"""Pipeline templates and the divide-and-conquer generator.

Semantics match the reference planner (SOSP '23 §4.1.2;
/root/reference/oobleck/csrc/planning/execution_result.h:60-204,
pipeline_template.cpp:82-339), re-termed for TPU: a *host* owns
`chips_per_host` chips (reference: node/GPU). For every feasible host count n
the generator finds the stage partition minimizing the t1+t2+t3 pipeline cost
model:

  stage latency  = Σ_layers (fwd+bwd)/chips + allreduce_in_host[chips] (if >1)
  t1 = Σ stage latencies
  t2 = (2·S + k* + 1) · latency(k*)        k* = bottleneck stage index
  t3 = Σ latencies of stages after k*
  mem(stage) = Σ 6·param_bytes + activation_bytes

Feasibility rules (pipeline_template.cpp:193-214): stages ≤ layers; multiple
hosts never share one stage; a single host needs chips ≥ stages; a one-stage
single-host assignment requires a power-of-2 chip count; in-host chip splits
are even bisections only.

Two interchangeable engines: this pure-Python implementation (reference
behavior, used in tests and as fallback) and the C++ one in
oobleck_tpu/csrc/planner.cpp (threaded, GIL-free, same memo key) loaded via
ctypes — `TemplateGenerator(engine="native")`; the default "auto" prefers
native with Python fallback.
"""

from __future__ import annotations

import logging
import math
from dataclasses import dataclass, field
from functools import lru_cache

logger = logging.getLogger("oobleck.planning")


@dataclass(frozen=True)
class LayerProfile:
    """Per-layer planning costs (reference LayerExecutionResult,
    execution_result.h:17-38). Times in milliseconds, memory in bytes."""

    layer_index: int
    forward: float
    backward: float
    allreduce_in_host: dict[int, float]      # chips -> time
    allreduce_across_hosts: dict[int, float]  # hosts -> time
    mem_params: int
    mem_activation: int

    def to_json(self) -> dict:
        return {
            "forward": self.forward,
            "backward": self.backward,
            "mem_required": [self.mem_params, self.mem_activation],
        }


@dataclass(frozen=True)
class StageSpec:
    """A contiguous layer range on one host slice (reference
    StageExecutionResult, execution_result.h:60-112)."""

    layer_indices: tuple[int, ...]
    num_chips: int
    forward: float
    backward: float
    mem_required: int

    @property
    def latency(self) -> float:
        return self.forward + self.backward

    @classmethod
    def build(cls, profiles: list[LayerProfile], start: int, end: int,
              num_chips: int,
              comm_hidden_fraction: float = 0.0) -> "StageSpec":
        """`comm_hidden_fraction` > 0 charges each layer its EFFECTIVE
        allreduce cost — max(0, ar - hf * layer_compute) — instead of the
        fully-serialized ar, matching a deployment that runs the overlapped
        step (parallel/overlap.py). 0.0 reproduces the reference cost model
        exactly."""
        from oobleck_tpu.parallel.overlap import effective_comm

        fwd = bwd = 0.0
        mem = 0
        for i in range(start, end):
            p = profiles[i]
            f = p.forward / num_chips
            b = p.backward / num_chips
            fwd += f
            bwd += b
            if num_chips > 1:
                ar = p.allreduce_in_host.get(num_chips, 0.0)
                fwd += effective_comm(ar, f, comm_hidden_fraction)
                bwd += effective_comm(ar, b, comm_hidden_fraction)
            mem += 6 * p.mem_params + p.mem_activation
        return cls(tuple(range(start, end)), num_chips, fwd, bwd, mem)


@dataclass(frozen=True)
class PipelineTemplate:
    """One optimal pipeline shape for a given host count (reference
    PipelineTemplate, pipeline_template.h:20-91)."""

    stages: tuple[StageSpec, ...]
    iteration_time: float
    num_layers: int
    num_hosts: int
    chips_per_host: int

    @property
    def num_stages(self) -> int:
        return len(self.stages)

    @property
    def num_chips(self) -> int:
        return sum(s.num_chips for s in self.stages)

    def layers_per_stage(self) -> list[tuple[int, int]]:
        return [(s.layer_indices[0], s.layer_indices[-1] + 1) for s in self.stages]

    def get_rank_grid(self, ranks: list[int]) -> dict[int, list[int]]:
        """layer index -> chips_per_host ranks, repeating when a stage holds
        fewer chips (reference pipeline_template.h:57-84)."""
        assert len(ranks) == self.num_chips, (len(ranks), self.num_chips)
        grid: dict[int, list[int]] = {}
        cursor = 0
        for stage in self.stages:
            stage_ranks = ranks[cursor:cursor + stage.num_chips]
            cursor += stage.num_chips
            repeat = self.chips_per_host // stage.num_chips
            layer_ranks: list[int] = []
            for r in stage_ranks:
                layer_ranks.extend([r] * repeat)
            for layer in stage.layer_indices:
                grid[layer] = layer_ranks
        return grid

    def mem_required_per_chip(self) -> int:
        return max(s.mem_required // s.num_chips for s in self.stages)

    def to_json(self) -> dict:
        return {
            "num_hosts": self.num_hosts,
            "chips_per_host": self.chips_per_host,
            "iteration_time": self.iteration_time,
            "stages": [
                {
                    "layers": [s.layer_indices[0], s.layer_indices[-1] + 1],
                    "num_chips": s.num_chips,
                    "forward": s.forward,
                    "backward": s.backward,
                    "mem_required": s.mem_required,
                }
                for s in self.stages
            ],
        }

    @classmethod
    def from_json(cls, d: dict, num_layers: int) -> "PipelineTemplate":
        stages = tuple(
            StageSpec(
                tuple(range(s["layers"][0], s["layers"][1])),
                s["num_chips"], s["forward"], s["backward"], s["mem_required"],
            )
            for d_s in [d["stages"]] for s in d_s
        )
        return cls(stages, d["iteration_time"], num_layers,
                   d["num_hosts"], d["chips_per_host"])


@dataclass
class _DCResult:
    """Divide-and-conquer cost node (reference DCExecutionResult,
    execution_result.h:114-204)."""

    t1: float
    t2: float
    t3: float
    kstar: int
    stages: tuple[StageSpec, ...]

    @property
    def t(self) -> float:
        return self.t1 + self.t2 + self.t3

    @property
    def kstar_latency(self) -> float:
        return self.stages[self.kstar].latency

    @classmethod
    def base(cls, stage: StageSpec, virtual_stages: int = 1) -> "_DCResult":
        lat = stage.latency
        return cls(t1=lat, t2=(2 / virtual_stages) * lat, t3=lat, kstar=0,
                   stages=(stage,))

    @classmethod
    def combine(cls, left: "_DCResult", right: "_DCResult",
                virtual_stages: int = 1) -> "_DCResult":
        if left.kstar_latency > right.kstar_latency:
            kstar = left.kstar
        else:
            kstar = right.kstar + len(left.stages)
        t1 = left.t1 + right.t1
        num_stages = len(left.stages) + len(right.stages)
        # The 2·S ramp term is the schedule's warmup+drain bubble; the
        # interleaved schedule runs it on 1/v-sized model chunks, so it
        # shrinks by the virtual-stage degree (bubble (S-1)/(v·M+S-1)).
        mb_factor = 2 * num_stages / virtual_stages + kstar + 1
        if kstar == left.kstar:
            t2 = mb_factor * left.kstar_latency
            t3 = sum(s.latency for s in left.stages[left.kstar:]) + \
                sum(s.latency for s in right.stages)
        else:
            t2 = mb_factor * right.kstar_latency
            t3 = sum(s.latency for s in right.stages[right.kstar:])
        return cls(t1=t1, t2=t2, t3=t3, kstar=kstar,
                   stages=left.stages + right.stages)


class TemplateGenerator:
    """Divide-and-conquer template search.

    `engine="python"` runs the in-process implementation below;
    `engine="native"` dispatches to the C++ planner (csrc/planner.cpp) and
    `engine="auto"` prefers native with Python fallback.
    """

    def __init__(self, engine: str = "auto"):
        self.engine = engine

    def create_pipeline_templates(
        self,
        profiles: list[LayerProfile],
        num_hosts: tuple[int, int],
        chips_per_host: int,
        virtual_stages: int = 1,
        comm_hidden_fraction: float = 0.0,
    ) -> list[PipelineTemplate]:
        """One min-cost template per feasible host count in
        [num_hosts[0], num_hosts[1]] (reference pipeline_template.cpp:82-161).

        virtual_stages > 1 evaluates the cost model under the interleaved
        schedule (warmup/drain ramp divided by v); comm_hidden_fraction > 0
        evaluates it under the overlapped step (allreduce discounted by the
        measured hidden fraction). Both are python-engine only — the C++
        planner predates the interleaved schedule and the overlap path.
        """
        if (self.engine in ("auto", "native") and virtual_stages == 1
                and comm_hidden_fraction == 0.0):
            try:
                from oobleck_tpu.planning import _native

                templates = _native.create_pipeline_templates(
                    profiles, num_hosts, chips_per_host
                )
                logger.info("pipeline templates from the native planner")
                return templates
            except Exception as e:  # noqa: BLE001 — auto falls back to python
                if self.engine == "native":
                    raise
                # Same templates, slower: say so, a missing compiler or a
                # broken build must not pass unnoticed.
                logger.warning(
                    "native planner unavailable (%s: %s); pipeline templates "
                    "from the Python planner", type(e).__name__, e)
        return _python_create_templates(profiles, num_hosts, chips_per_host,
                                        virtual_stages,
                                        comm_hidden_fraction)


def _python_create_templates(
    profiles: list[LayerProfile],
    num_hosts: tuple[int, int],
    chips_per_host: int,
    virtual_stages: int = 1,
    comm_hidden_fraction: float = 0.0,
) -> list[PipelineTemplate]:
    lo, hi = num_hosts
    num_layers = len(profiles)
    templates = []
    # One memo across every host count: keys include num_hosts, and multi-host
    # splits recurse into smaller host counts, so sharing is both safe and a
    # large win (the reference shares one dc_cache_ the same way). The
    # virtual-stage degree and comm-hidden fraction are fixed per call, so
    # they stay out of the key (the memo never outlives the call).
    memo: dict = {}
    for n in range(lo, hi + 1):
        best: _DCResult | None = None
        for num_stages in range(n, num_layers + 1):
            r = _dc(profiles, 0, num_layers, num_stages, n, chips_per_host,
                    memo, virtual_stages, comm_hidden_fraction)
            if r is not None and (best is None or r.t < best.t):
                best = r
        if best is None:
            continue
        templates.append(
            PipelineTemplate(best.stages, best.t, num_layers, n, chips_per_host)
        )
    return templates


def _dc(profiles, start, end, num_stages, num_hosts, chips_per_host, memo,
        virtual_stages: int = 1, comm_hidden_fraction: float = 0.0):
    """Reference divide_and_conquer (pipeline_template.cpp:166-339)."""
    key = (num_stages, start, end, num_hosts, chips_per_host)
    if key in memo:
        return memo[key]

    # Feasibility (pipeline_template.cpp:193-214)
    infeasible = False
    if num_stages > end - start:
        infeasible = True
    if num_hosts == 1:
        if chips_per_host < num_stages:
            infeasible = True
        if num_stages == 1 and (chips_per_host & (chips_per_host - 1)) != 0:
            infeasible = True
    elif num_hosts > num_stages:
        infeasible = True
    if infeasible:
        memo[key] = None
        return None

    # Base case
    if num_stages == 1:
        stage = StageSpec.build(profiles, start, end, chips_per_host,
                                comm_hidden_fraction)
        result = _DCResult.base(stage, virtual_stages)
        memo[key] = result
        return result

    best: _DCResult | None = None
    for k in range(start + 1, end):
        if num_hosts == 1:
            # Even in-host chip bisection only (cpp:243-247)
            half = chips_per_host // 2
            if half * 2 != chips_per_host or half == 0:
                continue
            for s_left in range(1, num_stages):
                left = _dc(profiles, start, k, s_left, 1, half, memo,
                           virtual_stages, comm_hidden_fraction)
                right = _dc(profiles, k, end, num_stages - s_left, 1,
                            chips_per_host - half, memo, virtual_stages,
                            comm_hidden_fraction)
                if left is None or right is None:
                    continue
                cand = _DCResult.combine(left, right, virtual_stages)
                if best is None or cand.t < best.t:
                    best = cand
        else:
            for h_left in range(1, num_hosts):
                for s_left in range(1, num_stages):
                    left = _dc(profiles, start, k, s_left, h_left,
                               chips_per_host, memo, virtual_stages,
                               comm_hidden_fraction)
                    right = _dc(profiles, k, end, num_stages - s_left,
                                num_hosts - h_left, chips_per_host, memo,
                                virtual_stages, comm_hidden_fraction)
                    if left is None or right is None:
                        continue
                    cand = _DCResult.combine(left, right, virtual_stages)
                    if best is None or cand.t < best.t:
                        best = cand

    memo[key] = best
    return best
