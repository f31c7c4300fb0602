"""oobleck_tpu — a TPU-native resilient distributed training framework.

A ground-up JAX/XLA re-design with the capabilities of SymbioticLab/Oobleck
(SOSP '23): fault-tolerant large-model training built on *pipeline templates*.
A planner (per-layer profiler + C++ divide-and-conquer template generator +
batch-distribution solver) precomputes optimal pipeline configurations for
every feasible node count; an elastic master/agent/worker control plane detects
host failures; and the execution engine re-instantiates heterogeneous pipelines
on the survivors and resumes within seconds.

Unlike the reference (PyTorch/DeepSpeed/NCCL), the compute path here is
idiomatic JAX: models are explicit layer lists (no fx tracing), pipeline
stages run as pjit/shard_map computations on TPU sub-meshes, stage-to-stage
activations move with `lax.ppermute` over ICI, and data-parallel gradient sync
uses `lax.psum` / cross-mesh transfers.

Layer map (mirrors reference SURVEY.md §1):
  L5 CLI            oobleck_tpu.elastic.run
  L4 Elastic        oobleck_tpu.elastic (master / agent / worker)
  L3 Planning       oobleck_tpu.planning (+ csrc C++ planner)
  L2 Model / data   oobleck_tpu.models, oobleck_tpu.execution.{dataset,dataloader}
  L1 Execution      oobleck_tpu.execution (engine / pipeline), oobleck_tpu.parallel
"""

__version__ = "0.1.0"
