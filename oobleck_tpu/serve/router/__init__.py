"""Multi-replica serving router — the L6 front door.

One address in front of N ServingPlane replicas. The serving plane
(layer below) made ONE replica resilient: hot-reloading weights from a
live training job, admission control, paged KV. This package makes the
REPLICA SET a single dependable endpoint, and closes the loop with the
chip pool so the set can grow under load and shrink on reclaim:

  registry.py   Who is routable: versioned self-registration handshake,
                /healthz probe loop with RTT EWMAs, DOWN on consecutive
                failures, weights-skew COOLING (a replica lagging the
                fleet's hot-reloads serves only as a last resort).
  routing.py    Where a request goes: prefix-affine rendezvous hashing
                over the SAME rolling page-chain hash the paged KV cache
                is keyed with, deadline-aware spill to
                power-of-two-choices, cooled replicas last.
  server.py     The proxy itself: ordered-candidate walk (429 spills,
                dead connections fail over — retried once when
                idempotent, fast 503 when not), one trace id per request
                across every hop, honest fleet-wide Retry-After when
                everyone is full.
  pressure.py   Fleet-wide PressureMonitor: the pool arbiter's borrow
                verdict fed by router aggregates instead of one
                replica's metrics.
  scale.py      Leases -> replicas: POOL_BORROW grants become registered
                replicas absorbing traffic; LEASE_RECLAIM drains them
                through the router with zero dropped requests.

``RouterPlane`` wires the pieces; tests compose the parts directly when
they need seams.

Env knobs: ``OOBLECK_ROUTER_PORT`` (listen port, 0 = ephemeral),
``OOBLECK_ROUTER_PROBE_S`` (health-probe period),
``OOBLECK_ROUTER_SKEW_MAX`` (hot-reloads behind fleet max before a
replica is cooled), ``OOBLECK_ROUTER_RETRY`` (failover retries for
idempotent requests). Replicas point ``OOBLECK_ROUTER_URL`` (or
``ServingPlane(router_url=...)``) at the router to self-register.
"""

from __future__ import annotations

from oobleck_tpu.serve.router.pressure import FleetPressureMonitor
from oobleck_tpu.serve.router.registry import (
    ROUTER_WIRE_V,
    Replica,
    ReplicaRegistry,
    deregister_from_router,
    register_with_router,
)
from oobleck_tpu.serve.router.routing import RoutingPolicy
from oobleck_tpu.serve.router.scale import ReplicaScaler
from oobleck_tpu.serve.router.server import RouterHTTPServer

__all__ = [
    "ROUTER_WIRE_V",
    "FleetPressureMonitor",
    "Replica",
    "ReplicaRegistry",
    "ReplicaScaler",
    "RouterHTTPServer",
    "RouterPlane",
    "RoutingPolicy",
    "deregister_from_router",
    "register_with_router",
]


class RouterPlane:
    """Registry + policy + HTTP proxy + fleet pressure, wired and
    lifecycle-managed. ``start()`` binds the port and begins probing;
    ``stop()`` tears both down. Replica scale-out is opt-in: hand
    ``attach_scaler`` a factory when the deployment can grow."""

    def __init__(self, *, port: int | None = None, host: str = "0.0.0.0",
                 probe_s: float | None = None, skew_max: int | None = None,
                 affinity: bool = True, retry_max: int | None = None,
                 proxy_timeout_s: float = 120.0, seed: int | None = None):
        self.registry = ReplicaRegistry(probe_s=probe_s, skew_max=skew_max)
        self.policy = RoutingPolicy(self.registry, affinity=affinity,
                                    seed=seed)
        self.server = RouterHTTPServer(
            self.registry, self.policy, port=port, host=host,
            proxy_timeout_s=proxy_timeout_s, retry_max=retry_max)
        self.pressure = FleetPressureMonitor()
        self.scaler: ReplicaScaler | None = None

    @property
    def port(self) -> int:
        return self.server.port

    def attach_scaler(self, factory, *, host: str = "127.0.0.1") \
            -> ReplicaScaler:
        self.scaler = ReplicaScaler(self.registry, factory, host=host)
        return self.scaler

    def start(self) -> "RouterPlane":
        self.registry.start()
        self.server.start()
        return self

    def stop(self) -> None:
        self.registry.stop()
        self.server.close()
