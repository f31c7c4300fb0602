"""Shared chip-pool arbiter: multi-tenant leases over one finite pool.

The elastic planes below this package (templates, grow incidents,
policy arms, proactive drain) each serve exactly ONE training job; this
package is the cross-tenant layer that lets several jobs and the serve
plane negotiate who restores, who degrades, and who yields chips:

    tenants.py   tenant registry (training jobs + serve replica groups,
                 each with a priority/SLO descriptor) and per-tenant
                 attributed goodput ledgers
    leases.py    chip leases with expiry — the unit of cross-tenant
                 chip movement, journaled so a restarted master still
                 knows who holds whose chips
    pressure.py  serve-side pressure monitor (queue depth, TTFT p99,
                 deadline_queued rate) that turns traffic peaks into
                 borrow requests with an SLO-debt price attached
    arbiter.py   the pool decision engine: borrow/reclaim arms scored
                 through the SAME classify->score->broadcast chain as
                 every other incident (policy/scorer.py, extended with
                 cross-tenant SLO-debt and preemption-cost terms)

The pool plane is inert unless ``OOBLECK_POOL=1``: a single-job cluster
pays one env read and keeps its exact pre-pool behavior.
"""

from oobleck_tpu.pool.arbiter import PoolArbiter, PoolDecision
from oobleck_tpu.pool.leases import ChipLease, LeaseBook
from oobleck_tpu.pool.pressure import PressureMonitor
from oobleck_tpu.pool.tenants import TenantRegistry, TenantSpec

__all__ = [
    "ChipLease",
    "LeaseBook",
    "PoolArbiter",
    "PoolDecision",
    "PressureMonitor",
    "TenantRegistry",
    "TenantSpec",
]
