"""Elastic fault-tolerant training jobs (ISSUE 13).

PAPER.md's cloud story (§Go runtime): an EDL master dispatches RecordIO
chunk tasks via etcd to STATELESS trainers, and a checkpointing pserver
makes the job durable — a dead trainer's claimed task times out and is
re-dispatched, a restarted trainer resumes from the checkpoint, and the
trainer fleet can shrink or grow while the job runs.  This module is
that story end to end on the TPU-native stack:

``ElasticTrainJob`` owns the WHOLE job state:

  * **membership** — the worker registers with the master under a TTL
    lease (``Master.register_worker``/``heartbeat`` — the etcd
    registration dir) and a background heartbeat keeps it alive; when
    the live set changes (a peer's lease expires on host loss, or a
    new peer joins), the job re-forms its mesh at the surviving extent
    at the next dispatch boundary and re-shards live state through the
    existing GSPMD machinery (the sharded-checkpoint contract, in
    memory);
  * **data** — master-dispatched record-range tasks drain through a
    ``FeedPipeline`` source generator, so the task pull + record read
    + batch build OVERLAP device compute on the staging thread;
    ``task_finished`` is acked only AFTER the covering dispatch has
    synced (the pipeline's ``on_delivered`` hook) AND — when
    checkpointing is on — the manifest covering that step has durably
    COMMITTED (the store's ``on_commit`` callback), so acked work is
    always in the durable params: a worker killed mid-dispatch OR
    mid-commit leaves its claims to lease-timeout and re-dispatch,
    exactly go/master/service.go's recovery (the checkpoint's master
    cursor counts commit-gated acks as done, so a whole-job restore
    agrees with the params);
  * **durability** — periodic ASYNC sharded checkpoints
    (``AsyncShardedCheckpoint``): params + optimizer accumulators +
    the master task cursor + reader position + RNG, captured as host
    copies at the delivered-dispatch boundary (donated-safe: the next
    dispatch may donate the device buffers) and WRITTEN on a
    background thread so the step loop never blocks, with atomic
    manifest commit (tmp + rename) and bounded retention.  A restarted
    or replacement worker resumes from the newest manifest and replays
    nothing: acked work is in the params, unacked claims re-dispatch.

Job-level gauges (tasks done/failed/requeued, checkpoint age/bytes/
stall, membership epoch) ride the PR 6 metrics-source registry and the
trace watchdog; ``tools/perf_gate.py elastic`` gates the async
checkpoint overhead and the kill-resume goodput.

The checkpoint cursor is only consistent when no dispatch runs ahead of
delivery, so a checkpointing job pins ``pipeline_depth=1`` (staging
still overlaps compute — the input-pipeline win the elastic lane
actually needs; the deeper in-flight window is a serving-lane
optimization).
"""

import base64
import json
import os
import shutil
import threading
import time

import numpy as np

__all__ = ['ElasticTrainJob', 'AsyncShardedCheckpoint',
           'CheckpointWriteError', 'ElasticJobError']

MANIFEST_FMT = 'paddle-tpu-elastic-manifest'
MANIFEST_VERSION = 1
_MANIFEST_PREFIX = 'MANIFEST-'
_SHARDS_DIR = 'shards'
# liveness marker (ISSUE 17 satellite): written at store open, removed
# at close — AsyncShardedCheckpoint.gc() never touches a dir carrying
# one, so cross-job retention cannot eat a running job's manifests
_ACTIVE_MARKER = 'ACTIVE'


class CheckpointWriteError(RuntimeError):
    """The background checkpoint writer failed; raised (once) from
    ``wait()``/``close()`` so a silent writer death cannot masquerade
    as durability."""


class ElasticJobError(RuntimeError):
    """An ElasticTrainJob configuration/state error."""


def _save_shard(path, arr):
    from ..fluid import io as fluid_io
    fluid_io._save_one(path, arr)


def _load_shard(path):
    from ..fluid import io as fluid_io
    return fluid_io._load_one(path)


class AsyncShardedCheckpoint(object):
    """Sharded checkpoint store with async writes, atomic manifest
    commit and bounded retention.

    Layout under ``directory``::

        MANIFEST-<step>.json        # commit point (tmp + os.replace)
        shards/<step>/<var_name>    # one LoDTensor-format file per var

    ``save(step, arrays, extras)`` enqueues HOST arrays for a
    background writer (latest-wins: a save landing while the previous
    one is still writing REPLACES it and counts a ``stall`` — the step
    loop never blocks on checkpoint IO).  The manifest is written only
    after every shard landed, via tmp + rename, so a crash mid-write
    leaves a ``.tmp`` shard dir and no manifest — swept (with every
    other orphan) on open and after each retention prune: no manifest
    ever references a missing shard, and no shard file outlives its
    manifest.

    ``sync=True`` writes inline on the caller thread (the measured
    comparator lane for perf_gate ``elastic``)."""

    def __init__(self, directory, keep=3, sync=False):
        self.directory = directory
        self.keep = max(int(keep), 1)
        self.sync = bool(sync)
        os.makedirs(os.path.join(directory, _SHARDS_DIR), exist_ok=True)
        self._cond = threading.Condition()
        self._pending = None
        self._busy_since = None
        self._thread = None
        self._closed = False
        self._error = None
        self._m = {'saves': 0, 'stalls': 0, 'errors': 0,
                   'bytes_written': 0, 'last_step': None,
                   'last_commit_t': None}
        with open(os.path.join(directory, _ACTIVE_MARKER), 'w') as f:
            json.dump({'pid': os.getpid(), 'opened_t': time.time()}, f)
        self._sweep()  # crashed-write hygiene from a previous life

    # ---- paths ---------------------------------------------------------

    def _manifest_path(self, step):
        return os.path.join(self.directory,
                            '%s%012d.json' % (_MANIFEST_PREFIX, step))

    def _shard_dir(self, step):
        return os.path.join(self.directory, _SHARDS_DIR, '%012d' % step)

    def _manifest_steps(self):
        out = []
        for f in os.listdir(self.directory):
            if f.startswith(_MANIFEST_PREFIX) and f.endswith('.json'):
                try:
                    out.append(int(f[len(_MANIFEST_PREFIX):-5]))
                except ValueError:
                    continue
        return sorted(out)

    # ---- write side ----------------------------------------------------

    def save(self, step, arrays, extras=None, wait=False,
             on_commit=None):
        """Checkpoint ``arrays`` (name -> array) at ``step``.  Host
        copies are taken HERE, synchronously — after ``save`` returns
        the caller may donate/mutate the device buffers freely; only
        the serialization + disk write is deferred to the writer
        thread.  ``extras`` must be JSON-serializable (the master
        cursor blob rides base64-encoded).  ``on_commit(step)`` runs
        right after the manifest commit (on the writer thread; inline
        for a sync store) — the elastic job's ack-release point: work
        is reported finished only once its covering state is durable.
        A latest-wins-replaced save's callback is NOT invoked; the
        newer save's commit covers it."""
        if self._closed:
            raise CheckpointWriteError('checkpoint store is closed')
        item = (int(step),
                {n: np.asarray(a) for n, a in arrays.items()},
                dict(extras or {}), on_commit)
        if self.sync:
            self._write(item)
            if on_commit is not None:
                on_commit(int(step))
            return
        with self._cond:
            if self._closed:
                raise CheckpointWriteError('checkpoint store is closed')
            if self._pending is not None:
                # latest-wins: never block the step loop, never queue
                # unboundedly — the dropped save is a counted stall
                self._m['stalls'] += 1
            self._pending = item
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._writer_loop,
                    name='ckpt-writer-%s' % os.path.basename(
                        self.directory.rstrip(os.sep)),
                    daemon=True)
                self._thread.start()
            self._cond.notify_all()
        if wait:
            self.wait()

    # an idle writer retires after this long; the next save() simply
    # starts a fresh one — so N short-lived checkpointing objects (e.g.
    # Trainers in a sweep) never accumulate N parked threads
    IDLE_EXIT_S = 5.0

    def _writer_loop(self):
        idle_deadline = time.time() + self.IDLE_EXIT_S
        while True:
            with self._cond:
                while self._pending is None and not self._closed:
                    if time.time() >= idle_deadline:
                        self._thread = None  # save() restarts us
                        return
                    self._cond.wait(0.1)
                if self._pending is None and self._closed:
                    return
                item, self._pending = self._pending, None
                self._busy_since = time.time()
            try:
                self._write(item)
                if item[3] is not None:
                    # the commit callback runs BEFORE the busy flag
                    # clears, so wait() returning implies callbacks ran
                    item[3](item[0])
            except BaseException as e:  # surfaced by wait()/close()
                self._error = e
                self._m['errors'] += 1
            finally:
                with self._cond:
                    self._busy_since = None
                    self._cond.notify_all()
            idle_deadline = time.time() + self.IDLE_EXIT_S

    def _write(self, item):
        step, arrays, extras = item[0], item[1], item[2]
        sdir = self._shard_dir(step)
        tmp = sdir + '.tmp'
        if os.path.isdir(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        shards, nbytes = {}, 0
        for name, arr in arrays.items():
            # var names may contain '/'-unsafe chars only in exotic
            # programs; keep the flat name (the manifest records it)
            _save_shard(os.path.join(tmp, name), arr)
            shards[name] = '%s/%012d/%s' % (_SHARDS_DIR, step, name)
            nbytes += int(arr.nbytes)
        if os.path.isdir(sdir):
            # re-commit of the same step (e.g. the final checkpoint at
            # a step a periodic save already committed): retract the
            # MANIFEST FIRST so a crash inside this window leaves "no
            # manifest for this step" (resume falls back to the
            # previous retained manifest) — never a committed manifest
            # pointing at deleted shards
            mpath = self._manifest_path(step)
            if os.path.exists(mpath):
                os.remove(mpath)
            shutil.rmtree(sdir)
        os.replace(tmp, sdir)
        manifest = {
            'fmt': MANIFEST_FMT, 'version': MANIFEST_VERSION,
            'step': step, 'shards': shards, 'bytes': nbytes,
            'time': time.time(), 'extras': extras,
        }
        mpath = self._manifest_path(step)
        mtmp = mpath + '.tmp'
        with open(mtmp, 'w') as f:
            json.dump(manifest, f)
        os.replace(mtmp, mpath)  # the atomic commit point
        self._m['saves'] += 1
        self._m['bytes_written'] += nbytes
        self._m['last_step'] = step
        self._m['last_commit_t'] = time.time()
        self._sweep()

    def _sweep(self):
        """Retention + hygiene: keep the newest ``keep`` manifests;
        remove pruned manifests FIRST, then their shard dirs; then
        sweep every orphan — shard dirs without a live manifest
        (crashed prune), ``.tmp`` shard dirs and manifest tmps
        (crashed write)."""
        steps = self._manifest_steps()
        for step in steps[:-self.keep]:
            try:
                os.remove(self._manifest_path(step))
            except OSError:
                pass
        live = set(steps[-self.keep:])
        shards_root = os.path.join(self.directory, _SHARDS_DIR)
        for d in os.listdir(shards_root):
            base = d[:-4] if d.endswith('.tmp') else d
            try:
                step = int(base)
            except ValueError:
                step = None
            if d.endswith('.tmp') or step is None or step not in live:
                shutil.rmtree(os.path.join(shards_root, d),
                              ignore_errors=True)
        for f in os.listdir(self.directory):
            if f.startswith(_MANIFEST_PREFIX) and f.endswith('.json.tmp'):
                try:
                    os.remove(os.path.join(self.directory, f))
                except OSError:
                    pass

    # ---- read side -----------------------------------------------------

    def latest(self):
        """The newest committed manifest dict, or None."""
        steps = self._manifest_steps()
        if not steps:
            return None
        with open(self._manifest_path(steps[-1])) as f:
            return json.load(f)

    def load(self, manifest=None):
        """(step, {name: array}, extras) for ``manifest`` (default:
        newest)."""
        manifest = manifest if manifest is not None else self.latest()
        if manifest is None:
            raise CheckpointWriteError(
                'no committed checkpoint manifest under %s'
                % self.directory)
        arrays = {
            name: _load_shard(os.path.join(self.directory,
                                           *rel.split('/')))
            for name, rel in manifest['shards'].items()
        }
        return int(manifest['step']), arrays, dict(
            manifest.get('extras') or {})

    # ---- lifecycle / observability -------------------------------------

    def pending_age(self):
        """Seconds the writer has been busy on the CURRENT write (None
        when idle) — the watchdog's checkpoint-stall probe."""
        since = self._busy_since
        return (time.time() - since) if since is not None else None

    def wait(self, timeout=30.0):
        """Block until the writer drained (pending save committed);
        raises CheckpointWriteError if the writer failed."""
        deadline = time.time() + timeout
        with self._cond:
            while (self._pending is not None or
                   self._busy_since is not None):
                left = deadline - time.time()
                if left <= 0:
                    raise CheckpointWriteError(
                        'checkpoint writer did not drain in %.1fs'
                        % timeout)
                self._cond.wait(min(left, 0.1))
        if self._error is not None:
            err, self._error = self._error, None
            raise CheckpointWriteError(
                'checkpoint write failed: %r' % (err, )) from err

    def metrics(self):
        m = dict(self._m)
        m['pending'] = self._pending is not None
        m['writing'] = self._busy_since is not None
        last = m['last_commit_t']
        m['age_s'] = (time.time() - last) if last else None
        return m

    @classmethod
    def gc(cls, root, keep_jobs=2, keep_hours=None):
        """Cross-job retention (ISSUE 17 satellite): ``root`` holds one
        checkpoint directory per job (the per-job stores already bound
        their own step retention with ``keep=``; what grows without
        bound is the number of FINISHED jobs).  Removes dead job dirs —
        committed manifests, shards and all — keeping the newest
        ``keep_jobs`` of them by last-manifest mtime.  ``keep_hours``
        (ISSUE 19 satellite) adds an age-based sweep on top of the
        count-based one: a dead store whose newest manifest is older
        than ``keep_hours`` hours is removed even when the
        ``keep_jobs`` count would have retained it.  Never touched:
        dirs carrying the ``ACTIVE`` marker (a live store; a crashed
        job's stale marker is the operator's to clear) and dirs that
        don't look like checkpoint stores at all (no manifests, no
        shards/).  Returns the removed paths."""
        if int(keep_jobs) < 0:
            raise ValueError('gc: keep_jobs must be >= 0')
        if keep_hours is not None and float(keep_hours) < 0:
            raise ValueError('gc: keep_hours must be >= 0')
        dead = []
        for name in sorted(os.listdir(root)):
            d = os.path.join(root, name)
            if not os.path.isdir(d):
                continue
            try:
                entries = os.listdir(d)
            except OSError:
                continue
            manifests = [f for f in entries
                         if f.startswith(_MANIFEST_PREFIX)
                         and f.endswith('.json')]
            if not manifests and _SHARDS_DIR not in entries:
                continue  # not a checkpoint store: never touch
            if _ACTIVE_MARKER in entries:
                continue  # live job: never touch
            newest = max([os.path.getmtime(os.path.join(d, f))
                          for f in manifests] or
                         [os.path.getmtime(d)])
            dead.append((newest, d))
        dead.sort()
        doomed = set(
            d for _, d in dead[:max(0, len(dead) - int(keep_jobs))])
        if keep_hours is not None:
            cutoff = time.time() - float(keep_hours) * 3600.0
            doomed.update(d for newest, d in dead if newest < cutoff)
        removed = []
        for _, d in dead:
            if d not in doomed:
                continue
            shutil.rmtree(d, ignore_errors=True)
            removed.append(d)
        return removed

    def close(self):
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        try:
            os.remove(os.path.join(self.directory, _ACTIVE_MARKER))
        except OSError:
            pass
        if self._error is not None:
            err, self._error = self._error, None
            raise CheckpointWriteError(
                'checkpoint write failed: %r' % (err, )) from err


class ElasticTrainJob(object):
    """One fault-tolerant elastic training job: master-fed data,
    heartbeat membership, async sharded checkpoints, dp shrink/grow.

    build_fn: rebuilds the model from scratch (a restarted worker must
        recreate identical var names, so it runs under
        ``unique_name.guard``); returns ``(main_program,
        startup_program, loss_var)``.
    master: an in-process ``distributed.Master`` or a ``MasterClient``
        dialing the job's ``MasterServer`` — the job only uses the
        shared get_task/task_finished/task_failed/new_pass/heartbeat/
        snapshot surface.  Pass ``endpoints=`` instead (master=None)
        to have the job own a ``ResilientMasterClient`` over that
        endpoint list (ISSUE 15): master RPCs then retry through
        transient faults, reconnect across a master restart and fail
        over in order to promoted standbys — the job rides a master
        restart mid-pass (reconnect -> the heartbeat re-registers ->
        epoch bump -> the existing mesh re-form path) instead of
        crashing on the first broken socket.  A task the failed-over
        master re-dispatches after THIS job already trained it (its
        ack died with the primary) is recognized by its record range
        and acked WITHOUT retraining — zero double-processed records
        across failover.
    ckpt_dir: the ``AsyncShardedCheckpoint`` directory; a newest
        manifest there is resumed from (params + optimizer
        accumulators + RNG restored; the master cursor rides the
        manifest for whole-job restarts via ``restore_master=True``).
    batch_fn: ``batch_fn(records) -> feed dict`` — one claimed task's
        raw record bytes become one training step's batch.
    mesh_for: ``mesh_for(n_live_workers) -> axes dict`` (e.g.
        ``lambda n: {'dp': 2 * n}``) — the job forms its mesh over the
        first ``prod(axes)`` devices and RE-FORMS it when membership
        changes; None runs the single-device ``Executor`` lane.
    steps_per_dispatch: tasks trained per device dispatch (the scan K).
    checkpoint_every: checkpoint every N delivered dispatches (0/None
        disables periodic checkpoints; the final state still commits).
    task_hook: ``task_hook(tid, task, ordinal)`` called on the staging
        thread right after a claim — test crash site (an exception here
        is a worker crash: claims are left to lease-timeout).
    """

    def __init__(self, build_fn, master, ckpt_dir, batch_fn,
                 worker_id='worker-0', steps_per_dispatch=1,
                 pipeline_depth=1, checkpoint_every=1,
                 keep_checkpoints=3, sync_checkpoints=False,
                 mesh_for=None, pass_num=1, poll_interval=0.05,
                 heartbeat_interval=1.0, task_hook=None, name=None,
                 watchdog_stall_s=None, restore_master=False,
                 fetch_list=None, endpoints=None, retry_policy=None):
        self._owns_master = False
        if endpoints is not None:
            if master is not None:
                raise ElasticJobError(
                    'pass master= OR endpoints=, not both')
            from .transport import ResilientMasterClient
            master = ResilientMasterClient(endpoints,
                                           retry=retry_policy)
            self._owns_master = True
        elif retry_policy is not None:
            raise ElasticJobError(
                'retry_policy= only applies to the endpoints= lane '
                '(an explicit master= owns its own fault handling)')
        if int(pipeline_depth) > 1 and checkpoint_every:
            # the checkpoint cursor reads the scope at delivery time;
            # a dispatch issued AHEAD of the delivered one would already
            # have advanced it past the acked tasks
            raise ElasticJobError(
                'a checkpointing ElasticTrainJob needs pipeline_depth=1 '
                '(the cursor must not run ahead of acked tasks); got '
                'depth %d' % int(pipeline_depth))
        if master is None:
            raise ElasticJobError(
                'ElasticTrainJob needs master= or endpoints=')
        self.build_fn = build_fn
        self.master = master
        self.ckpt_dir = ckpt_dir
        self.batch_fn = batch_fn
        self.worker_id = worker_id
        self.steps_per_dispatch = int(steps_per_dispatch)
        self.pipeline_depth = int(pipeline_depth)
        self.checkpoint_every = int(checkpoint_every or 0)
        self.keep_checkpoints = int(keep_checkpoints)
        self.sync_checkpoints = bool(sync_checkpoints)
        self.mesh_for = mesh_for
        self.pass_num = int(pass_num)
        self.poll_interval = float(poll_interval)
        self.heartbeat_interval = float(heartbeat_interval)
        self.task_hook = task_hook
        self.watchdog_stall_s = watchdog_stall_s
        self.restore_master = bool(restore_master)
        self._extra_fetches = list(fetch_list or [])
        self.name = name or ('elastic-%s' % worker_id)

        self.resumed = False
        self.start_step = 0
        self.step = 0
        self.tasks_done = []
        self.losses = []
        self.ckpt = None
        self._exe = None
        self._scope = None
        self._main = self._startup = self._loss = None
        self._scanners = {}
        self._claims = {}  # ordinal -> (tid, task key)
        self._claims_lock = threading.Lock()
        # record ranges THIS job has delivered, mapped to the step
        # whose dispatch delivered them (their updates are in the live
        # params as of that step): a failed-over master re-dispatching
        # one — the ack died with the primary — is acked without
        # retraining.  The step gates that ack on durability when
        # checkpointing is on: ack-after-durability holds for dedup
        # acks exactly like trained acks.
        self._processed = {}
        self._dedup_pending = []  # staged dedup acks: (step, tid)
        # delivered-but-unacked tasks, each tagged with the step whose
        # manifest must COMMIT before the ack may go out (the
        # ack-after-durability contract; flushed by the store's
        # on_commit callback).  With checkpointing disabled there is
        # no durability to wait for and acks go out at delivery.
        self._pending_acks = []
        self._acks_lock = threading.Lock()
        self._ordinal = 0
        self._window_base = 0
        self._delivered_dispatches = 0
        self._cur_pass = 0
        self._pass_done = False
        self._stop = False
        self._resize_pending = False
        self._live = []
        self._formed_live = None  # the live set the executor is FOR
        self._epoch = 0
        self._members_lock = threading.Lock()
        self._hb_stop = None
        self._hb_thread = None
        self._m = {'tasks_done': 0, 'tasks_failed': 0,
                   'tasks_requeued': 0, 'tasks_deduped': 0,
                   'membership_epoch': 0,
                   'resizes': 0, 'dispatches': 0, 'heartbeats': 0,
                   'heartbeat_errors': 0, 'dp_extent': 0}
        self._metrics_key = None
        self._watchdog_probe = None

    # ---- membership ----------------------------------------------------

    def _note_members(self, epoch, workers):
        with self._members_lock:
            self._epoch = int(epoch)
            self._m['membership_epoch'] = self._epoch
            self._live = list(workers)
            # a resize is pending iff the live set differs from the set
            # the CURRENT executor was formed for — comparing against
            # _formed_live (not the previous observation) means a
            # change landing while the executor is still being built is
            # caught by _make_executor's own post-build check instead
            # of silently swallowed
            if self.mesh_for is not None and \
                    self._formed_live is not None and \
                    self._live != self._formed_live:
                self._resize_pending = True

    def _heartbeat_loop(self):
        while not self._hb_stop.wait(self.heartbeat_interval):
            try:
                epoch, workers = self.master.heartbeat(self.worker_id)
                self._m['heartbeats'] += 1
                self._note_members(epoch, workers)
            except Exception:
                # a dead master door: keep trying — the job itself will
                # fail on its next claim if the master is truly gone
                self._m['heartbeat_errors'] += 1

    def members(self):
        """(epoch, live worker ids) as last seen by the heartbeat."""
        with self._members_lock:
            return self._epoch, list(self._live)

    # ---- build / resume ------------------------------------------------

    def _build(self):
        import paddle_tpu.fluid as fluid
        self.ckpt = AsyncShardedCheckpoint(
            self.ckpt_dir, keep=self.keep_checkpoints,
            sync=self.sync_checkpoints)
        with fluid.unique_name.guard():
            self._main, self._startup, self._loss = self.build_fn()
        self._scope = fluid.core.Scope()
        exe0 = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(self._scope):
            exe0.run(self._startup)
        self._rng_state = None
        manifest = self.ckpt.latest()
        if manifest is not None:
            step, arrays, extras = self.ckpt.load(manifest)
            with fluid.scope_guard(self._scope):
                for name, arr in arrays.items():
                    self._scope.var(name).set_value(arr)
            self.resumed = True
            self.start_step = self.step = step
            self._rng_state = extras.get('rng')
            self._cur_pass = int(extras.get('pass', 0))
            if self.restore_master and extras.get('master'):
                # whole-job restart: the manifest's cursor blob brings
                # the task queue back to the acked frontier (claimed
                # tasks return to todo — nothing replays, nothing is
                # lost)
                if not hasattr(self.master, 'restore'):
                    raise ElasticJobError(
                        'restore_master=True needs an in-process '
                        'Master (a MasterClient cannot rewrite the '
                        'remote queue); got %r' % type(self.master))
                self.master.restore(
                    base64.b64decode(extras['master']))

    def _persistable_names(self):
        from ..fluid import io as fluid_io
        return [v.name for v in self._main.list_vars()
                if fluid_io.is_persistable(v)]

    def _state_arrays(self):
        """Host copies of every persistable (params + optimizer
        accumulators), donated-safe: taken NOW, before the next
        dispatch can donate the device buffers."""
        from ..fluid import core
        out = {}
        for name in self._persistable_names():
            var = self._scope.find_var(name)
            if var is None or var.value() is None:
                continue
            val = var.value()
            if isinstance(val, core.LoDTensor):
                out[name] = val.numpy()
            else:
                out[name] = np.asarray(val)
        return out

    def _rng_snapshot(self):
        exe = self._exe
        if exe is None:
            return None
        if hasattr(exe, '_mesh'):
            key = exe._rng
            return None if key is None else \
                ['pe'] + [int(v) for v in np.asarray(key).ravel()]
        if exe._rng is None:
            return None
        return ['exe', int(exe._rng_seed), int(exe._rng)]

    def _rng_restore(self, state):
        if not state:
            return
        exe = self._exe
        if state[0] == 'pe' and hasattr(exe, '_mesh'):
            import jax.numpy as jnp
            exe._rng = jnp.asarray(np.array(state[1:], np.uint32))
        elif state[0] == 'exe' and not hasattr(exe, '_mesh'):
            exe._rng_seed, exe._rng = int(state[1]), int(state[2])

    def _make_executor(self):
        import paddle_tpu.fluid as fluid
        from paddle_tpu import parallel
        rng = self._rng_snapshot() or self._rng_state
        with self._members_lock:
            formed_for = list(self._live)
        if self.mesh_for is None:
            self._exe = fluid.Executor(fluid.default_place())
            self._m['dp_extent'] = 1
        else:
            import jax
            n_live = max(1, len(formed_for))
            axes = dict(self.mesh_for(n_live))
            total = int(np.prod([s for s in axes.values()]))
            devices = jax.devices()[:total]
            if len(devices) < total:
                raise ElasticJobError(
                    'mesh_for(%d) wants %d devices, only %d exist'
                    % (n_live, total, len(devices)))
            mesh = parallel.make_mesh(axes, devices=devices)
            self._exe = fluid.ParallelExecutor(
                loss_name=self._loss.name, main_program=self._main,
                scope=self._scope, mesh=mesh)
            self._m['dp_extent'] = self._exe._dp_extent()
        self._rng_restore(rng)
        self._rng_state = None
        with self._members_lock:
            # the executor is now FOR formed_for; a membership change
            # that landed DURING the (slow) build re-arms the resize
            # instead of being lost
            self._formed_live = formed_for
            self._resize_pending = (self.mesh_for is not None and
                                    self._live != formed_for)

    def _gather_state_to_host(self):
        """Pull every persistable back to a host array in the scope so
        the NEXT executor re-shards it onto the new mesh (the in-memory
        form of the sharded-checkpoint save/load round trip)."""
        import paddle_tpu.fluid as fluid
        with fluid.scope_guard(self._scope):
            for name, arr in self._state_arrays().items():
                self._scope.var(name).set_value(arr)

    # ---- data ----------------------------------------------------------

    def _read_range(self, task):
        from ..runtime import native
        path = task['path']
        entry = self._scanners.get(path)
        if entry is None or entry[1] > task['start']:
            if entry is not None:
                entry[0].close()
            entry = [native.RecordIOScanner(path), 0]
            self._scanners[path] = entry
        scanner, pos = entry
        records = []
        try:
            while pos < task['start'] + task['count']:
                rec = next(scanner)
                if pos >= task['start']:
                    records.append(rec)
                pos += 1
        finally:
            entry[1] = pos
        return records

    def _task_source(self):
        """The FeedPipeline source: claim -> read -> batch, one yield
        per task, run on the STAGING thread so the whole pull overlaps
        device compute.  Stops at pass end or a pending resize.  Pass
        advancement is SHARED-safe (ISSUE 14): several workers drain
        one master and each reports pass end, so the advance is
        ``new_pass(expected=)`` on the pass this source observed — a
        peer's earlier advance makes ours a no-op instead of a double
        cursor bump (or a mid-pass recycle of the next pass's done
        tasks)."""
        master_pass = self.master.current_pass()
        while not self._stop and not self._resize_pending:
            tid, task = self.master.get_task()
            if tid == -1:
                self._cur_pass += 1
                # the dedup set is PER PASS: the next pass's re-
                # dispatch of every range is legitimate new work — a
                # stale entry would silently skip training the whole
                # pass (it also bounds the set's growth)
                self._processed.clear()
                if self._cur_pass >= self.pass_num:
                    self._pass_done = True
                    return
                if self.master.new_pass(expected=master_pass):
                    master_pass += 1
                else:
                    # a peer worker advanced first: resync to the
                    # master's cursor instead of double-advancing
                    master_pass = self.master.current_pass()
                continue
            if task is None:
                # nothing claimable RIGHT NOW: either a peer holds
                # claims, or OUR delivered-but-unacked tasks keep the
                # master's pending set nonempty (acks gate on a
                # manifest commit) — a frontier checkpoint releases
                # them, or the pass could never reach -1
                self._maybe_flush_frontier()
                time.sleep(self.poll_interval)
                continue
            key = (task['path'], int(task['start']),
                   int(task['count']))
            done_step = self._processed.get(key)
            if done_step is not None:
                # a failed-over (or restarted) master re-dispatched a
                # range this job already trained — the ack died with
                # the primary.  The update is in our params: ack it,
                # never retrain it (double-processing would skew the
                # final params vs a fault-free run).  Under
                # checkpointing the ack gates on durability like any
                # other: immediate only once a manifest covering the
                # delivering step committed, else staged for the
                # store's on_commit release.
                durable = True
                if self.checkpoint_every and self.ckpt is not None:
                    last = self.ckpt.metrics()['last_step']
                    durable = last is not None and last >= done_step
                if durable:
                    self.master.task_finished(tid)
                    self._m['tasks_deduped'] += 1
                else:
                    with self._acks_lock:
                        self._dedup_pending.append((done_step, tid))
                continue
            ordinal = self._ordinal
            with self._claims_lock:
                self._claims[ordinal] = (tid, key)
            if self.task_hook is not None:
                # crash site for the fault tests: an exception here is
                # a worker death — the claim above lease-times-out and
                # re-dispatches
                self.task_hook(tid, task, ordinal)
            try:
                records = self._read_range(task)
                feed = self.batch_fn(records)
            except Exception:
                # a bad chunk read fails the task back for another
                # trainer (or retry) — cloud_reader's contract
                with self._claims_lock:
                    self._claims.pop(ordinal, None)
                entry = self._scanners.pop(task['path'], None)
                if entry is not None:
                    entry[0].close()
                self.master.task_failed(tid)
                self._m['tasks_failed'] += 1
                continue
            self._ordinal += 1
            yield feed

    def _on_delivered(self, ordinals, fetches):
        """The pipeline's post-sync hook: the dispatch covering these
        source ordinals has completed on device — the step cursor
        advances and a checkpoint boundary may capture a consistent
        (params, cursor) pair.  The tasks' ACKS are only STAGED here:
        ``task_finished`` goes out when a manifest covering this step
        COMMITS (the store's on_commit callback), so a crash between
        delivery and durability re-dispatches the tasks and the
        replacement retrains them from a manifest that excludes them —
        acked work is ALWAYS in the durable params.  (The residual
        window — manifest committed, ack still in flight when the
        worker dies — re-trains a task whose update was already saved,
        the same at-least-once boundary as the reference's in-flight
        TaskFinished RPC.)  With checkpointing disabled acks go out
        immediately."""
        # pipeline ordinals are window-local (a re-formed mesh gets a
        # fresh pipeline counting from 0); the job's claim keys are
        # global, offset by the window's first ordinal
        ordinals = [self._window_base + o for o in ordinals]
        delivered = []
        with self._claims_lock:
            for o in ordinals:
                ent = self._claims.pop(o, None)
                if ent is not None:
                    delivered.append(ent[0])
                    self._processed[ent[1]] = self.step + len(ordinals)
        self.step += len(ordinals)
        self._m['dispatches'] += 1
        self._delivered_dispatches += 1
        if self.checkpoint_every:
            with self._acks_lock:
                self._pending_acks.extend(
                    (self.step, tid) for tid in delivered)
        else:
            self._send_acks(delivered)
        if fetches:
            try:
                self.losses.append(float(np.asarray(fetches[0]).ravel()[0]))
            except (TypeError, ValueError, IndexError):
                pass
        if self.checkpoint_every and \
                self._delivered_dispatches % self.checkpoint_every == 0:
            self.checkpoint()

    def _send_acks(self, tids):
        for tid in tids:
            self.master.task_finished(tid)
        self.tasks_done.extend(tids)
        self._m['tasks_done'] += len(tids)

    def _flush_acks_up_to(self, committed_step):
        """The store's on_commit callback: release every staged ack
        whose covering step is now durable — trained acks and staged
        DEDUP acks (re-dispatched ranges whose delivering step had
        not committed yet) alike."""
        with self._acks_lock:
            ready = [tid for s, tid in self._pending_acks
                     if s <= committed_step]
            self._pending_acks = [(s, tid) for s, tid in
                                  self._pending_acks
                                  if s > committed_step]
            dedup_ready = [tid for s, tid in self._dedup_pending
                           if s <= committed_step]
            self._dedup_pending = [(s, tid) for s, tid in
                                   self._dedup_pending
                                   if s > committed_step]
        self._send_acks(ready)
        for tid in dedup_ready:
            self.master.task_finished(tid)
            self._m['tasks_deduped'] += 1

    def _maybe_flush_frontier(self):
        """Ack-after-durability's liveness guard: when every claim is
        delivered, staged acks are waiting, and no save is in flight,
        take a frontier checkpoint — its commit releases the acks.
        Safe from the staging thread: all claims delivered plus the
        depth-1 pipeline means no dispatch is mutating the scope (the
        run thread is blocked on the staging queue)."""
        if not self.checkpoint_every or self.ckpt is None:
            return
        with self._acks_lock:
            if not self._pending_acks and not self._dedup_pending:
                return
        with self._claims_lock:
            if self._claims:
                return  # a dispatch may still be in flight
        m = self.ckpt.metrics()
        if m['pending'] or m['writing']:
            return  # that save's commit will flush the acks
        self.checkpoint()

    # ---- durability ----------------------------------------------------

    def _master_cursor(self):
        """The master queue state as an envelope blob (b64 str), via
        whichever surface this job's master exposes — rewritten so
        tasks whose updates are IN the params being checkpointed (acks
        staged, waiting on this very manifest's commit) count as done:
        a whole-job restore must not re-dispatch work the params
        already hold.  Staged acks are read BEFORE the snapshot, so an
        ack flushing in between is completed twice — a no-op."""
        with self._acks_lock:
            # staged DEDUP acks are in the params too (their update
            # landed at their original delivery): the cursor rewrite
            # completes both kinds
            staged = [tid for _s, tid in self._pending_acks] + \
                [tid for _s, tid in self._dedup_pending]
        try:
            if hasattr(self.master, 'snapshot'):
                blob = self.master.snapshot()
            elif hasattr(self.master, 'fetch_snapshot'):
                blob, _seq = self.master.fetch_snapshot()
            else:
                return None
            if staged:
                from .master import complete_tasks_in_blob
                blob = complete_tasks_in_blob(blob, staged)
        except Exception:
            return None  # a cursor-less checkpoint still resumes params
        return base64.b64encode(blob).decode()

    def checkpoint(self, wait=False):
        """Capture (params + accumulators, master cursor, reader
        position, RNG) at the current delivered frontier and hand it to
        the async writer."""
        extras = {
            'step': self.step,
            'pass': self._cur_pass,
            'rng': self._rng_snapshot(),
            'worker': self.worker_id,
            'epoch': self._epoch,
            'master': self._master_cursor(),
        }
        self.ckpt.save(self.step, self._state_arrays(), extras,
                       wait=wait, on_commit=self._flush_acks_up_to)

    # ---- the run loop --------------------------------------------------

    def _run_window(self):
        """One FeedPipeline lifetime: runs until pass end, a pending
        resize, or a source crash (which propagates — crash
        semantics)."""
        from ..fluid.dataflow import FeedPipeline
        import paddle_tpu.fluid as fluid
        self._window_base = self._ordinal
        fetch_list = [self._loss] + self._extra_fetches
        kwargs = {}
        if not hasattr(self._exe, '_mesh'):
            kwargs = {'program': self._main, 'scope': self._scope}
        pipe = FeedPipeline(
            self._exe, fetch_list=fetch_list,
            source=self._task_source(),
            steps=self.steps_per_dispatch,
            pipeline_depth=self.pipeline_depth,
            name='%s-pipe' % self.name,
            watchdog_stall_s=self.watchdog_stall_s,
            on_delivered=self._on_delivered, **kwargs)
        try:
            with fluid.scope_guard(self._scope):
                for _ in pipe:
                    pass  # acks/steps/checkpoints ride _on_delivered
        finally:
            self._last_pipe_metrics = pipe.metrics()
            # a crash-path close never re-raises here: the iteration
            # above already delivered the typed error once
            pipe.close()

    def _requeue_unacked(self):
        """Safety sweep at a clean window boundary: fail back any
        claim that never reached a delivered dispatch so the re-formed
        job (or a peer) gets it immediately instead of waiting out the
        lease."""
        with self._claims_lock:
            pending = list(self._claims.items())
            self._claims.clear()
        for _ordinal, (tid, _key) in pending:
            try:
                self.master.task_failed(tid)
                self._m['tasks_requeued'] += 1
            except Exception:
                pass  # the lease will expire on its own

    def _resize(self):
        """Re-form the mesh at the surviving extent: host-ify live
        state, rebuild the executor over the new mesh (GSPMD re-shards
        on the next dispatch), resume draining."""
        self._requeue_unacked()
        self._gather_state_to_host()
        self._make_executor()  # owns re-arming/clearing _resize_pending
        self._m['resizes'] += 1

    def _register_observability(self):
        from ..fluid import profiler as _profiler
        from ..fluid import trace as _trace
        import weakref
        ref = weakref.ref(self)
        self._metrics_fn = lambda: (ref().metrics() if ref() else None)
        self._metrics_key = _profiler.register_metrics_source(
            self.name, self._metrics_fn)
        weakref.finalize(self, _profiler.unregister_metrics_source,
                         self._metrics_key, self._metrics_fn)
        if self.watchdog_stall_s is not None:
            def age(ref=ref):
                job = ref()
                return job.ckpt.pending_age() if job and job.ckpt \
                    else None
            self._watchdog_probe = _trace.watchdog.register(
                'elastic/%s/checkpoint_stall' % self.name, age,
                float(self.watchdog_stall_s))
            self._watchdog_age_fn = age
            weakref.finalize(self, _trace.watchdog.unregister,
                             self._watchdog_probe, age)
            if hasattr(self.master, 'unreachable_age'):
                # master-unreachable probe (ISSUE 15): the resilient
                # client tracks how long the control plane has been
                # continuously failing — a dead master past the stall
                # threshold dumps the flight recorder once per episode
                def m_age(ref=ref):
                    job = ref()
                    return job.master.unreachable_age() if job \
                        else None
                self._master_probe = _trace.watchdog.register(
                    'elastic/%s/master_unreachable' % self.name,
                    m_age, float(self.watchdog_stall_s))
                self._master_age_fn = m_age
                weakref.finalize(self, _trace.watchdog.unregister,
                                 self._master_probe, m_age)

    def run(self):
        """Drive the job to the end of its pass budget.  Crash
        semantics on error: heartbeats stop, claims are left to
        lease-timeout, the exception propagates (a replacement job over
        the same ckpt_dir resumes from the newest manifest)."""
        epoch, workers = self.master.register_worker(self.worker_id)
        self._note_members(epoch, workers)
        self._hb_stop = threading.Event()
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop, name='%s-hb' % self.name,
            daemon=True)
        self._hb_thread.start()
        self._register_observability()
        try:
            self._build()
            self._make_executor()
            while not self._pass_done and not self._stop:
                self._run_window()
                if self._resize_pending and not self._pass_done:
                    self._resize()
            # final durable state: commit and WAIT (the job is done —
            # there is no step loop left to overlap with)
            if self.ckpt is not None:
                self.checkpoint(wait=not self.sync_checkpoints)
            # stop heartbeats BEFORE deregistering: a racing renewal
            # after the deregister would re-register this finished
            # worker as a ghost member (and spuriously resize peers)
            self._stop_heartbeat()
            self._deregister()
            return self
        except BaseException:
            self._abort()
            raise
        finally:
            self._stop_heartbeat()
            for entry in self._scanners.values():
                entry[0].close()
            self._scanners.clear()

    def stop(self):
        """Graceful stop request (takes effect at the next claim)."""
        self._stop = True

    def _deregister(self):
        try:
            self.master.deregister_worker(self.worker_id)
        except Exception:
            pass

    def _abort(self):
        """Crash semantics: claims stay (their leases will expire and
        re-dispatch), no deregistration — the master sees exactly what
        it would see of a dead host.  The checkpoint writer is drained
        (best effort) so the in-process crash SIMULATION quiesces to
        one of the two real post-mortem states — manifest committed
        AND its acks flushed, or neither — never a half-state where a
        later background commit races the replacement's resume."""
        self._stop = True
        if self.ckpt is not None:
            try:
                self.ckpt.wait(timeout=30)
            except Exception:
                pass

    def _stop_heartbeat(self):
        if self._hb_stop is not None:
            self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=5)
            self._hb_thread = None

    def metrics(self):
        m = dict(self._m)
        m['step'] = self.step
        m['start_step'] = self.start_step
        m['resumed'] = self.resumed
        if self.ckpt is not None:
            ck = self.ckpt.metrics()
            m['checkpoint_age_s'] = ck.pop('age_s')
            m['checkpoint_bytes'] = ck['bytes_written']
            m['checkpoint_stalls'] = ck['stalls']
            m['checkpoint'] = ck
        if hasattr(self.master, 'metrics'):
            # the resilient-lane gauges (ISSUE 15): how hard the
            # control plane is working to stay connected
            mc = self.master.metrics()
            m['master_retries'] = mc.get('retries', 0)
            m['master_reconnects'] = mc.get('reconnects', 0)
            m['master_failovers'] = mc.get('failovers', 0)
            m['master_unreachable_s'] = mc.get('unreachable_s')
            m['master_client'] = mc
        return m

    def close(self):
        """Release the checkpoint writer (idempotent)."""
        self._stop_heartbeat()
        if self.ckpt is not None:
            self.ckpt.close()
        if self._owns_master:
            try:
                self.master.close()
            except Exception:
                pass
