"""Distribution layer — two independent stories share this package:

* **solver serving** (:mod:`repro.parallel.batch`,
  :mod:`repro.parallel.scheduler`): the B axis of ``qniht_batch`` sharded
  over a 1-D ``batch`` mesh, the same computation per item; the continuous-batching
  scheduler refills freed rows of the live state from an admission queue.
* **model training** (:mod:`repro.parallel.sharding`,
  :mod:`repro.parallel.collectives`): parameter sharding rules and quantized
  gradient collectives for the LM-twin workloads.
"""
from repro.parallel.batch import (
    BatchServer,
    make_batch_mesh,
    pad_batch,
    pad_state,
    refill_rows,
    sharded_qniht_run,
    sharded_segment_run,
    state_shardings,
    strip_state,
)
from repro.parallel.journal import ChunkJournal
from repro.parallel.scheduler import (
    AdmissionQueue,
    ContinuousScheduler,
    Request,
    RequestReport,
    segment_step,
)
from repro.parallel.collectives import (
    fake_grad_compression,
    make_qgrad_allreduce,
    quantized_allreduce_mean,
)
from repro.parallel.sharding import (
    batch_axes,
    batch_spec,
    params_shardings,
    replicated,
    spec_for_path,
)

__all__ = [
    "AdmissionQueue",
    "BatchServer",
    "ChunkJournal",
    "ContinuousScheduler",
    "Request",
    "RequestReport",
    "make_batch_mesh",
    "pad_batch",
    "pad_state",
    "refill_rows",
    "segment_step",
    "sharded_qniht_run",
    "sharded_segment_run",
    "state_shardings",
    "strip_state",
    "fake_grad_compression",
    "make_qgrad_allreduce",
    "quantized_allreduce_mean",
    "batch_axes",
    "batch_spec",
    "params_shardings",
    "replicated",
    "spec_for_path",
]
