"""RESPECT core — the paper's contribution as a composable library.

Layers (bottom-up):

* graph/costmodel — the scheduling IR and the pipelined-accelerator model;
* sampler/embedding — synthetic training distribution + paper's embedding;
* exact/heuristic/rho/postprocess — the solver zoo (imitation targets and
  baselines) and the deployment mapping;
* segment — the jittable rho + repair twins the fused serving path and the
  RL reward share;
* ptrnet/rl — the LSTM pointer network and its REINFORCE trainer;
* respect — the deployable scheduler facade;
* dnn_graphs — Table-I real-model graphs;
* partitioner — the TPU-pod adaptation (transformer blocks -> pipeline
  stages on a v5e mesh).
"""

from .batching import BucketedDecoder, PaddedGraphBatch, bucket_for, pack_padded  # noqa: F401
from .costmodel import EDGETPU, PipelineSystem, PodSystem, evaluate_schedule  # noqa: F401
from .dnn_graphs import MODEL_SPECS, all_model_graphs, build_model_graph  # noqa: F401
from .embedding import embed_dim, embed_graph  # noqa: F401
from .exact import brute_force_monotone, exact_bb, exact_dp, order_from_assignment  # noqa: F401
from .graph import CompGraph, InvalidGraphError, validate_graph, validate_monotone  # noqa: F401
from .heuristic import compiler_partition, heuristic_schedule_many, list_schedule  # noqa: F401
from .postprocess import repair  # noqa: F401
from .respect import RespectScheduler  # noqa: F401
from .rho import rho  # noqa: F401
from .sampler import DagSampler, prefetch, sample_batch, sample_dag  # noqa: F401
from .segment import exact_dp_batch, exact_dp_jax, repair_jax, rho_dp_jax  # noqa: F401
