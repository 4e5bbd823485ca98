"""hetu_tpu — a TPU-native deep learning framework.

A ground-up JAX/XLA/Pallas re-design with the capability surface of Hetu
(PKU DAIR's dataflow DL system, see SURVEY.md): define-then-run graph API,
executor, distributed strategies (DP/TP/PP/EP/CP) over ``jax.sharding`` device
meshes, MoE, host-resident embedding store with bounded-staleness cache,
auto-parallel search, tokenizers/ONNX/metrics tooling.

Typical use (identical shape to reference examples)::

    import hetu_tpu as ht
    x = ht.placeholder_op('x')
    w = ht.init.xavier_uniform((784, 10), name='w')
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(ht.matmul_op(x, w), y_), [0])
    train_op = ht.optim.SGDOptimizer(0.1).minimize(loss)
    executor = ht.Executor({'train': [loss, train_op]})
    executor.run('train', feed_dict={...})
"""

from . import initializers as init
from . import optim
from .optim import lr_scheduler as lr  # reference alias: ht.lr.StepScheduler
from . import context as _context_mod
from .context import (cpu, gpu, tpu, rcpu, rgpu, DLContext, DeviceGroup,
                      context, current_context, get_current_context,
                      DistConfig, make_mesh)
from .ndarray import (NDArray, NDSparseArray, array, empty, sparse_array,
                      IndexedSlices, is_gpu_ctx)
from .graph import (Op, PlaceholderOp, Variable, placeholder_op, gradients,
                    GradientOp, Executor, topo_sort,
                    worker_init, worker_finish, server_init, server_finish,
                    scheduler_init, scheduler_finish)
from .ops import *  # noqa: F401,F403 — full op surface (ht.matmul_op, ...)
from .data import Dataloader, DataloaderOp, GNNDataLoaderOp, dataloader_op
from . import data
from . import parallel
from . import parallel as dist  # reference alias: ht.dist.DataParallel
from .parallel.dispatch import dispatch
from .parallel.pipeline import pipeline_block, PipelineParallel
from .parallel.ring_attention import ContextParallel
from . import layers
from . import metrics
from . import obs
from . import chaos
from . import tokenizers
from .profiler import HetuProfiler, CollectiveProfiler
# reference script compat: ht.NCCLProfiler is the collectives
# profiler's name there (profiler.py:390); same surface here
NCCLProfiler = CollectiveProfiler
from . import analysis
from .analysis import lint, GraphValidationError
from . import autoparallel
from . import onnx
from . import gnn
from . import graphboard
from . import launcher
from .gnn import csrmm_op, csrmv_op, gcn_aggregate_op
from .launcher import init_distributed
from . import ps
from .ps import (EmbeddingStore, CacheSparseTable, ps_embedding_lookup_op,
                 default_store)
from . import serving
from .serving import InferenceExecutor, ServingRouter, ServeRejected

__version__ = "0.1.0"
