"""Generate API.spec: the pinned public Python API surface.

Reference: tools/diff_api.py + paddle/fluid/API.spec — CI fails when a
public signature changes without updating the spec.  Run:

    python tools/gen_api_spec.py > paddle_tpu/API.spec
"""

import inspect
import sys


def _spec_of(fn):
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return '(unavailable)'
    return str(sig)


def _walk(prefix, mod, names):
    lines = []
    for name in names:
        obj = getattr(mod, name, None)
        if obj is None:
            continue
        full = '%s.%s' % (prefix, name)
        if inspect.isclass(obj):
            lines.append('%s.__init__ %s' % (full, _spec_of(obj.__init__)))
            for mname, meth in sorted(vars(obj).items()):
                if mname.startswith('_'):
                    continue
                if callable(meth):
                    lines.append('%s.%s %s' % (full, mname, _spec_of(meth)))
        elif callable(obj):
            lines.append('%s %s' % (full, _spec_of(obj)))
    return lines


def generate():
    import paddle_tpu.fluid as fluid
    import paddle_tpu.serving as serving

    lines = []
    lines += _walk('paddle_tpu.serving', serving,
                   sorted(serving.__all__))
    lines += _walk('paddle_tpu.fluid.layers', fluid.layers,
                   sorted(fluid.layers.__all__))
    lines += _walk('paddle_tpu.fluid.optimizer', fluid.optimizer,
                   sorted(fluid.optimizer.__all__))
    lines += _walk('paddle_tpu.fluid', fluid, [
        'Executor', 'ParallelExecutor', 'Program', 'Operator', 'Variable',
        'Parameter', 'DataFeeder', 'DistributeTranspiler',
        'DistributeTranspilerConfig', 'InferenceTranspiler', 'Trainer',
        'Inferencer', 'CheckpointConfig', 'BeginEpochEvent',
        'EndEpochEvent', 'BeginStepEvent', 'EndStepEvent', 'CPUPlace',
        'TPUPlace', 'CUDAPlace', 'CUDAPinnedPlace', 'default_place',
        'LoDTensor',
        'LoDTensorArray', 'Scope', 'ParamAttr', 'WeightNormParamAttr',
        'ExecutionStrategy', 'BuildStrategy', 'scope_guard',
        'program_guard', 'name_scope', 'append_backward', 'get_var',
        'global_scope', 'create_lod_tensor', 'create_random_int_lodtensor',
        'default_main_program', 'default_startup_program',
        'memory_optimize', 'release_memory', 'Go', 'Select', 'make_channel',
        'channel_send', 'channel_recv', 'channel_close',
    ])
    lines += _walk('paddle_tpu.fluid.dataflow', fluid.dataflow,
                   sorted(fluid.dataflow.__all__))
    lines += _walk('paddle_tpu.fluid.trace', fluid.trace,
                   sorted(fluid.trace.__all__))
    lines += _walk('paddle_tpu.fluid.io', fluid.io, sorted(
        n for n in fluid.io.__all__ if not n.startswith('_')))
    lines += _walk('paddle_tpu.fluid.metrics', fluid.metrics, [
        'Accuracy', 'Auc', 'ChunkEvaluator', 'CompositeMetric',
        'DetectionMAP', 'EditDistance', 'Precision', 'Recall',
    ])
    lines += _walk('paddle_tpu.fluid.nets', fluid.nets,
                   sorted(fluid.nets.__all__))
    lines += _walk('paddle_tpu.fluid.initializer', fluid.initializer, [
        'Constant', 'Uniform', 'Normal', 'Xavier', 'MSRA', 'Bilinear',
        'ConstantInitializer', 'UniformInitializer', 'NormalInitializer',
        'XavierInitializer', 'MSRAInitializer', 'BilinearInitializer',
        'force_init_on_cpu', 'init_on_cpu',
    ])
    lines += _walk('paddle_tpu.fluid.regularizer', fluid.regularizer, [
        'L1Decay', 'L2Decay', 'L1DecayRegularizer', 'L2DecayRegularizer',
    ])
    lines += _walk('paddle_tpu.fluid.clip', fluid.clip, [
        'ErrorClipByValue', 'GradientClipByValue', 'GradientClipByNorm',
        'GradientClipByGlobalNorm',
    ])
    lines += _walk('paddle_tpu.fluid.profiler', fluid.profiler, [
        'profiler', 'cuda_profiler', 'reset_profiler', 'start_profiler',
        'stop_profiler',
    ])
    lines += _walk('paddle_tpu.fluid.unique_name', fluid.unique_name, [
        'generate', 'guard', 'switch',
    ])
    lines += _walk('paddle_tpu.fluid.backward', fluid.backward, [
        'append_backward', 'calc_gradient',
    ])
    lines += _walk('paddle_tpu.fluid.transpiler', fluid.transpiler, [
        'DistributeTranspiler', 'DistributeTranspilerConfig',
        'InferenceTranspiler', 'HashName', 'RoundRobin', 'memory_optimize',
        'release_memory',
    ])
    lines += _walk('paddle_tpu.fluid.contrib', fluid.contrib, [
        'InitState', 'StateCell', 'TrainingDecoder', 'BeamSearchDecoder',
        'memory_usage',
    ])
    lines += _walk('paddle_tpu.fluid.recordio_writer', fluid.recordio_writer,
                   ['convert_reader_to_recordio_file',
                    'convert_reader_to_recordio_files'])
    # the distributed runtime surface (ISSUE 12: the two-tier embedding
    # cache lives here next to its AsyncSparseEmbedding host tier;
    # ISSUE 13: the elastic job + its checkpoint store and the master's
    # membership/snapshot doors; ISSUE 15: the resilient transport
    # lane + the fault-injection seam + snapshot replication; ISSUE 17:
    # the transport generalized into a service-agnostic substrate —
    # the Master* error names are back-compat aliases; ISSUE 19: the
    # parameter-server embedding tier — sharded row-range pservers
    # behind that substrate)
    import paddle_tpu.distributed as distributed
    lines += _walk('paddle_tpu.distributed', distributed, [
        'AsyncSparseEmbedding', 'AsyncSparseClosedError',
        'CachedEmbeddingTable', 'EmbedCacheCapacityError',
        'optimizer_accumulator_vars',
        'ElasticTrainJob', 'AsyncShardedCheckpoint',
        'CheckpointWriteError', 'ElasticJobError',
        'Master', 'MasterServer', 'MasterClient',
        'ResilientMasterClient', 'ResilientServiceClient',
        'RetryPolicy', 'ServiceServer', 'DedupWindow',
        'MasterUnavailableError', 'MasterProtocolError',
        'ServiceUnavailableError', 'ServiceProtocolError',
        'FaultInjector', 'InjectedFault', 'SnapshotReplica',
        'PServerShard', 'ShardedEmbeddingClient',
        'shard_row_ranges', 'sharded_cache_from_scope',
    ])
    return sorted(set(lines))


if __name__ == '__main__':
    sys.stdout.write('\n'.join(generate()) + '\n')
