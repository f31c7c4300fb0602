"""Master-side fleet-health aggregator: robust per-host stats + straggler
detection.

Each agent heartbeat carries a telemetry digest (obs/telemetry.py); this
tracker keeps one row per host — latest digest, a step-time EWMA, and the
cross-fleet robust statistics (median / MAD z-score, ratio-vs-median)
that make a *relatively* slow host stand out regardless of the absolute
step time of the moment.

Detection is deliberately conservative, because the cost of a false
positive is a drained healthy host:

*   **robust, not mean/stddev** — one straggler inflates a mean badly
    enough to hide itself; the median/MAD pair is immune to the very
    outlier it is hunting.
*   **two independent thresholds** — the ratio-vs-median gate catches
    "meaningfully slower than the fleet" in absolute terms; the z-gate
    (applied when the fleet is large enough for MAD to mean anything)
    catches "statistically impossible under this fleet's spread".
*   **persistence hysteresis** — a host must breach on
    ``OOBLECK_STRAGGLER_PERSIST`` *consecutive* digests before it is
    flagged. A transient blip (GC pause, one slow input batch) resets to
    zero on the first healthy digest and never raises an incident.
*   **one flag per host** — ``consume_straggler()`` hands each flagged
    host out exactly once; the flag stays latched until ``clear(ip)``
    (the host was drained, lost, or re-registered), so a persistent
    straggler can never raise a second SLOWDOWN incident for the same
    degradation.

Knobs (read at construction; the sim injects explicit values instead):
    OOBLECK_STRAGGLER_RATIO     breach when step_s >= ratio * fleet
                                median (default 1.5)
    OOBLECK_STRAGGLER_Z         robust z threshold, fleets of >= 4 hosts
                                (default 3.0)
    OOBLECK_STRAGGLER_PERSIST   consecutive breaching digests before the
                                flag raises (default 3)
"""

from __future__ import annotations

import logging
import os
import time

logger = logging.getLogger("oobleck.obs")

ENV_RATIO = "OOBLECK_STRAGGLER_RATIO"
ENV_Z = "OOBLECK_STRAGGLER_Z"
ENV_PERSIST = "OOBLECK_STRAGGLER_PERSIST"

DEFAULT_RATIO = 1.5
DEFAULT_Z = 3.0
DEFAULT_PERSIST = 3

# MAD->sigma consistency constant for normal data: z = 0.6745*(x-med)/MAD.
MAD_SCALE = 0.6745
# Below this many reporting hosts the MAD is too degenerate to gate on;
# the ratio threshold alone decides.
MIN_HOSTS_FOR_Z = 4
# Step-time EWMA weight of the newest digest.
EWMA_ALPHA = 0.3


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "")
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


class _HostRow:
    __slots__ = ("digest", "ewma_s", "breaches", "flagged", "consumed",
                 "updated_at", "epoch", "z", "ratio")

    def __init__(self):
        self.digest: dict = {}
        self.ewma_s: float | None = None
        self.breaches = 0
        self.flagged = False
        self.consumed = False
        self.updated_at = 0.0
        self.epoch: int | None = None
        self.z: float | None = None
        self.ratio: float | None = None


class FleetTracker:
    """Per-host telemetry rows + straggler flags for the master.

    Not thread-safe by itself: the master's single event loop serializes
    ingestion, exactly like HostHealthTracker."""

    def __init__(self, *, clock=time.monotonic,
                 ratio: float | None = None, z: float | None = None,
                 persist: int | None = None):
        self._clock = clock
        self.ratio_threshold = (ratio if ratio is not None
                                else _env_float(ENV_RATIO, DEFAULT_RATIO))
        self.z_threshold = (z if z is not None
                            else _env_float(ENV_Z, DEFAULT_Z))
        self.persist = max(int(persist if persist is not None
                               else _env_float(ENV_PERSIST,
                                               DEFAULT_PERSIST)), 1)
        self._hosts: dict[str, _HostRow] = {}
        self._stale_digests = 0

    # -- ingestion ---------------------------------------------------------- #

    def ingest(self, ip: str, digest: dict, *,
               epoch: int | None = None,
               min_epoch: int | None = None) -> None:
        """Fold one heartbeat digest in and re-judge the host.

        ``min_epoch`` is the master's own epoch: a digest stamped with an
        OLDER epoch came from an agent that has not yet seen the fenced
        restart and describes a dead incarnation's steps — counted and
        dropped, mirroring the broadcast-side epoch fence."""
        if (min_epoch is not None and epoch is not None
                and epoch < min_epoch):
            self._stale_digests += 1
            return
        row = self._hosts.setdefault(ip, _HostRow())
        row.digest = dict(digest)
        row.epoch = epoch
        row.updated_at = self._clock()
        step_s = digest.get("step_s")
        if isinstance(step_s, (int, float)) and step_s > 0:
            row.ewma_s = (step_s if row.ewma_s is None else
                          (1 - EWMA_ALPHA) * row.ewma_s
                          + EWMA_ALPHA * step_s)
        self._judge(ip, row)

    def _judge(self, ip: str, row: _HostRow) -> None:
        """Recompute this host's z/ratio against the fleet and advance or
        reset its persistence counter."""
        step_s = row.digest.get("step_s")
        if not isinstance(step_s, (int, float)) or step_s <= 0:
            return
        peers = [r.digest.get("step_s") for r in self._hosts.values()]
        peers = sorted(v for v in peers
                       if isinstance(v, (int, float)) and v > 0)
        n = len(peers)
        if n < 2:
            return  # a fleet of one has no "relatively slow"
        med = peers[n // 2] if n % 2 else (peers[n // 2 - 1]
                                           + peers[n // 2]) / 2
        if med <= 0:
            return
        row.ratio = round(step_s / med, 6)
        mad = sorted(abs(v - med) for v in peers)[n // 2]
        row.z = (round(MAD_SCALE * (step_s - med) / mad, 6)
                 if mad > 0 else None)

        breach = row.ratio >= self.ratio_threshold and (
            n < MIN_HOSTS_FOR_Z or row.z is None
            or row.z >= self.z_threshold)
        if breach:
            row.breaches += 1
            if row.breaches >= self.persist and not row.flagged:
                row.flagged = True
                logger.warning(
                    "fleet: host %s flagged as straggler "
                    "(step=%.4fs median=%.4fs ratio=%.2f z=%s "
                    "breaches=%d)", ip, step_s, med, row.ratio,
                    row.z, row.breaches)
        else:
            # Healthy digest: the persistence counter resets (a blip dies
            # here), but an already-raised flag stays latched until
            # clear() — recovery does not un-raise the incident.
            row.breaches = 0

    # -- flag lifecycle ----------------------------------------------------- #

    def consume_straggler(self) -> str | None:
        """One-shot: the next flagged-but-unconsumed host ip, or None.
        Each flag is handed out exactly once — the dedup that makes one
        sustained slowdown exactly ONE SLOWDOWN incident."""
        for ip in sorted(self._hosts):
            row = self._hosts[ip]
            if row.flagged and not row.consumed:
                row.consumed = True
                return ip
        return None

    def flagged(self) -> list[str]:
        return sorted(ip for ip, r in self._hosts.items() if r.flagged)

    def ratio(self, ip: str) -> float | None:
        """Latest step-time ratio vs the fleet median for one host (the
        slowdown severity the policy arms are priced with)."""
        row = self._hosts.get(ip)
        return row.ratio if row is not None else None

    def clear(self, ip: str) -> None:
        """Drop a host's row and flag (drained, lost, or re-registered —
        its next digests describe a different life)."""
        self._hosts.pop(ip, None)

    # -- /status ------------------------------------------------------------ #

    def snapshot(self) -> dict:
        """Bounded per-host view for the master's /status fleet_health
        block."""
        now = self._clock()
        hosts = {}
        for ip, row in sorted(self._hosts.items()):
            hosts[ip] = {
                "step_s": row.digest.get("step_s"),
                "ewma_s": round(row.ewma_s, 6) if row.ewma_s else None,
                "z": row.z,
                "ratio": row.ratio,
                "breaches": row.breaches,
                "flagged": row.flagged,
                "step": row.digest.get("step"),
                "age_s": round(now - row.updated_at, 3),
                # Why it is slow, where its agent says (obs/telemetry.py):
                # held dispatching, held on the device, between steps, or
                # out of device memory. Absent from a legacy agent's digest.
                "cause": {k: row.digest[k] for k in (
                    "dispatch_s", "readback_s", "between_s", "hbm_free_frac")
                    if k in row.digest},
            }
        return {
            "hosts": hosts,
            "flagged": self.flagged(),
            "stale_digests": self._stale_digests,
            "thresholds": {
                "ratio": self.ratio_threshold,
                "z": self.z_threshold,
                "persist": self.persist,
            },
        }
