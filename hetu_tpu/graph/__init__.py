from .node import (Op, PlaceholderOp, Variable, placeholder_op, topo_sort,
                   LowerCtx, name_scope)
from .gradients import gradients, GradientOp
from .executor import Executor, SubExecutor, worker_init, worker_finish, \
    server_init, server_finish, scheduler_init, scheduler_finish
