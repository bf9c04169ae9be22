"""parsec_tpu — a TPU-native task-based runtime with the capabilities of
PaRSEC (DAG scheduling, PTG + DTD DSLs, tiled distributed collections,
asynchronous dataflow communication, per-device task incarnations,
tracing/profiling), designed for JAX/XLA/Pallas/PJRT rather than ported.

Public API mirrors the reference's surface (parsec/runtime.h):

    ctx = parsec_tpu.init(nb_cores=4)
    tp = parsec_tpu.dtd.taskpool_new()
    ctx.add_taskpool(tp)
    tp.insert_task(body, (tile, parsec_tpu.dtd.INOUT))
    tp.wait()
    ctx.fini()
"""
from .utils.compile_cache import configure_compile_cache

configure_compile_cache()   # before anything below can compile

from .runtime.context import Context, init  # noqa: E402
from .runtime.compound import CompoundTaskpool, compose
from .runtime.recursive import recursive_call
from .runtime.taskpool import (Chore, Dep, Flow, HookReturn, Task, TaskClass,
                               Taskpool, TaskStatus)
from .data.data import Coherency, Data, DataCopy, FlowAccess, data_new_with_payload
from .data.datatype import Datatype, dtt_of_array
from .data.arena import Arena
from .utils.params import params
from . import dsl
from . import obs
from .dsl import dtd

__version__ = "0.1.0"

__all__ = [
    "Context", "init", "Taskpool", "TaskClass", "Task", "Chore", "Flow",
    "Dep", "HookReturn", "TaskStatus", "Data", "DataCopy", "Coherency",
    "FlowAccess", "Datatype", "Arena", "params", "dtd", "dsl", "obs",
    "CompoundTaskpool", "compose", "recursive_call",
    "data_new_with_payload", "dtt_of_array", "__version__",
]
