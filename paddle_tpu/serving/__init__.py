"""paddle_tpu.serving — the TPU-native inference serving stack.

Single-model: ``InferenceEngine`` serves a loaded inference program
(fluid.io.load_inference_model) request-facing — dynamic micro-batching
(MicroBatcher), shape-bucketed compiles (ShapeBucketSet), pipelined
multi-step eval dispatch (Executor.run_eval_multi /
ParallelExecutor.run_eval_multi for dp>1 sharded serving), and engine
metrics surfaced through fluid.profiler's timeline.

Generation: an engine built with ``generation=GenerationSpec(...)``
gains ``submit_generate`` — a continuous-batching autoregressive decode
lane: prompts prefill through the normal micro-batch/bucketing
machinery, per-request decoder state (KV/hidden) lives in a slot-based
``SlotStateCache`` resident in HBM, and an in-jit decode scan
(Executor.run_decode_multi / ParallelExecutor.run_decode_multi) runs K
greedy steps per dispatch over the whole slot batch with per-request
stop conditions masked inside — token-identical to per-request decode
at a fraction of the dispatches.

Multi-model: ``ModelRegistry`` hosts N named engines over one shared
device/mesh with cross-model HBM arbitration (``HBMArbiter``) —
budgeted admission, LRU weight eviction to host memory with transparent
reload, a fair request router, and per-model ``:serving/<model>``
timeline rows.  See engine.py / registry.py for the designs and the
README 'Serving engine' / 'Multi-model serving' sections for the knobs.

Pipelined decode (ISSUE 9): the decode lane keeps up to
``decode_pipeline_depth`` chained scans in flight — scan N+1 is
enqueued against scan N's device-resident (donated) output carry while
the host harvests N's token block asynchronously, so device
utilization no longer pays a host round trip per scan; shedding and
admission use per-signature ``ServiceTimeProfile`` estimates and the
registry's overload watermarks can track drain-vs-arrival rates
(``ServingConfig(adaptive_admission=True)``).

SLOs (ISSUE 8): requests carry ``priority`` and ``deadline_ms`` —
lot formation is deadline-aware (EDF within priority classes) and
past-deadline work is SHED with a typed ``DeadlineExceededError``
instead of served late; the registry refuses requests at the door with
``OverloadedError`` (+ retry-after hint) once a model's queue crosses
its depth/age watermarks; ``registry.warm()`` records a replayable
compile catalog inside the persistent compile cache directory and
``registry.prewarm()`` replays it so a restarted fleet compiles
nothing on first traffic; and ``OpenLoopLoadGen`` (loadgen.py) drives
the whole stack with seeded Poisson arrivals, reporting sustained
req/s, p50/p99/p99.9 and goodput.  README 'Serving SLOs' has the
operator's view; tools/load_gen.py is the CLI.

Fleet tier (ISSUE 17): ``ReplicaServer`` serves one registry over the
shared RPC substrate (distributed/transport.py — typed errors, seeded
retries, exactly-once dedup) and ``FleetRouter`` fronts N replicas
with load-balanced dispatch, decode-session affinity (``session=``
pins a generation's decode state to one replica), fleet-level typed
overload, and replica-death failover that re-prefills in-flight
generations on a survivor — token-identical under greedy decode.  See
fleet.py and the README 'Serving fleet' section.

    reg = serving.ModelRegistry(hbm_budget_bytes=2 << 30)
    reg.load('ranker', '/models/ranker')
    with reg:                                  # starts every worker
        fut = reg.submit('ranker', {'img': x})
        logits, = fut.result()
    print(reg.status())
"""

from .arbiter import HBMArbiter, HBMBudgetError  # noqa: F401
from .batcher import InferenceRequest, MicroBatcher  # noqa: F401
from .buckets import ShapeBucketSet, TrailingDimBuckets  # noqa: F401
from .decode import GenerationRequest, GenerationSpec, \
    SlotStateCache  # noqa: F401
from .engine import InferenceEngine, ServingConfig  # noqa: F401
from .errors import DeadlineExceededError, EngineClosedError, \
    OverloadedError  # noqa: F401
from .fleet import FleetFuture, FleetRouter, ReplicaServer  # noqa: F401
from .loadgen import OpenLoopLoadGen, TrafficClass  # noqa: F401
from .metrics import EngineMetrics  # noqa: F401
from .profile import ServiceTimeProfile  # noqa: F401
from .registry import ModelRegistry  # noqa: F401

__all__ = ['InferenceEngine', 'ServingConfig', 'MicroBatcher',
           'InferenceRequest', 'ShapeBucketSet', 'TrailingDimBuckets',
           'EngineMetrics', 'ModelRegistry', 'HBMArbiter',
           'HBMBudgetError', 'GenerationSpec', 'GenerationRequest',
           'SlotStateCache', 'DeadlineExceededError', 'OverloadedError',
           'EngineClosedError', 'OpenLoopLoadGen', 'TrafficClass',
           'ServiceTimeProfile', 'ReplicaServer', 'FleetRouter',
           'FleetFuture']
