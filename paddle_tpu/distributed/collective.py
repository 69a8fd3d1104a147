"""Collective ops (ref: python/paddle/distributed/collective.py →
paddle/fluid/operators/collective/c_allreduce_op.h etc.).

TPU-native: inside a mapped region (shard_map / fleet parallel step) each op
lowers to the XLA collective (psum / all_gather / ppermute / all_to_all)
over the named mesh axis, riding ICI.  Outside a mapped region there are
two cases: a single-process world, where they are identities; and a
multi-process launch (``jax.distributed`` initialized — the reference's
gloo control-plane case), where they aggregate host values across
processes via ``jax.experimental.multihost_utils``.  The eager cross-
process path is control-plane machinery (metrics, LocalSGD parameter
averaging, file sharding); the data plane stays inside mapped regions.

Subset-``group`` eager collectives still require EVERY live process to
make the call (the underlying gather is global); only member rows enter
the reduction and non-members get their input back.  send/recv keep the
single-process buffer emulation — a true cross-process p2p pair would
deadlock a global collective, matching the reference's restriction of
gloo send/recv to in-graph ops.

The active axis name is provided by the surrounding parallel context
(fleet sets it when entering tensor/data-parallel regions).
"""
from __future__ import annotations

import contextlib
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from ..ops.dispatch import call
from ..tensor.tensor import Tensor
from ..testing import faults as _faults


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


class Group:
    def __init__(self, rank, nranks, id=0, ranks=None, axis_name=None):
        self.rank = rank
        self.nranks = nranks
        self.id = id
        self.ranks = ranks or list(range(nranks))
        self.axis_name = axis_name  # mesh axis this group reduces over

    def is_member(self):
        return self.rank >= 0

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    @property
    def world_size(self):
        return self.nranks

    @property
    def process_group(self):
        return self

    def __repr__(self):
        return f"Group(rank={self.rank}, nranks={self.nranks}, axis={self.axis_name})"


_group_map = {}
_default_group = None

# axis-name stack installed by parallel contexts (shard_map bodies)
_axis_stack = []


@contextlib.contextmanager
def collective_axis(axis_name):
    """Install the mesh axis that collectives should reduce over; used by
    fleet/shard_map wrappers around parallel step functions."""
    _axis_stack.append(axis_name)
    try:
        yield
    finally:
        _axis_stack.pop()


def _current_axis(group=None):
    if group is not None and group.axis_name:
        return group.axis_name
    return _axis_stack[-1] if _axis_stack else None


def _process_count():
    try:
        return jax.process_count()
    except Exception:                                      # noqa: BLE001
        return 1


_kv_seq = [0]
_KV_TIMEOUT_MS = 60_000


class CollectiveTimeout(RuntimeError):
    """A rendezvous/transport wait expired: some rank never showed up.
    Carries everything the operator needs to find the dead rank — the op,
    the group, the bucket (for reducer collectives), and which ranks DID
    contribute before the deadline."""

    def __init__(self, op, timeout_ms, group=None, bucket=None,
                 ranks_seen=None, nranks=None, detail=""):
        self.op = op
        self.group = group
        self.bucket = bucket
        self.ranks_seen = ranks_seen
        msg = f"collective '{op}' timed out after {timeout_ms}ms"
        if bucket is not None:
            msg += f" (bucket {bucket})"
        msg += f" in group {group if group is not None else 'WORLD'}"
        if ranks_seen is not None and nranks:
            missing = sorted(set(range(nranks)) - set(ranks_seen))
            msg += (f"; ranks seen before the deadline: "
                    f"{sorted(ranks_seen)} of {nranks} — missing "
                    f"{missing}: those processes are hung or dead")
        if detail:
            msg += f" ({detail})"
        msg += ("; tune PADDLE_COLLECTIVE_TIMEOUT (seconds) for slow "
                "interconnects")
        super().__init__(msg)


# watchdog counters, surfaced through profiler.fast_path_summary(); a
# VIEW over the observability registry's "watchdog" family (same storage)
from ..observability import metrics as _metrics
from ..observability import timeline as _timeline

_watchdog_stats = _metrics.stats_family("watchdog", {
    "collective_timeouts": 0,   # waits that expired into CollectiveTimeout
    "kv_retries": 0,            # transient KV-store op failures absorbed
})


def watchdog_stats():
    return dict(_watchdog_stats)


def reset_watchdog_stats():
    for k in _watchdog_stats:
        _watchdog_stats[k] = 0


def _collective_timeout_ms():
    """Configurable rendezvous deadline (PADDLE_COLLECTIVE_TIMEOUT,
    seconds; default 60).  Read per call so operators and tests can tune
    a live process."""
    try:
        return max(int(float(os.environ.get(
            "PADDLE_COLLECTIVE_TIMEOUT", "60")) * 1000), 1)
    except ValueError:
        return _KV_TIMEOUT_MS


def _is_deadline(err):
    msg = str(err).lower()
    return "deadline" in msg or "timed out" in msg or "timeout" in msg


def _is_transient(err):
    """Coordinator-hiccup-shaped failures worth retrying.  Anything else
    (AttributeError on a missing client, pickling bugs, ...) is a real
    error that retrying would only mask.  Narrower than
    _dist_bootstrap._transient on purpose: mid-training deadlines are
    watchdog events (CollectiveTimeout), never retries, while at
    bootstrap a deadline just means peers have not arrived yet."""
    if isinstance(err, _faults.InjectedFault):
        return True
    msg = str(err).lower()
    return any(s in msg for s in (
        "unavailable", "connection", "reset", "broken pipe", "aborted",
        "internal", "try again"))


def _kv_call(client, method, *args):
    """One KV-store/coordination-service op with bounded retry-with-
    backoff on transient failures (the coordinator riding a restarting
    pod emits UNAVAILABLE-shaped errors that resolve in milliseconds).
    Deadline expiries are NOT retried — the caller turns them into a
    diagnosable CollectiveTimeout — and neither are non-transient
    errors.  A transient error that survives every retry is re-raised
    as-is; rendezvous call sites (:func:`_kv_allgather`, :func:`barrier`)
    convert THAT into a CollectiveTimeout too (op/group/ranks named)
    rather than surfacing a bare KV error mid-collective."""
    retries = int(os.environ.get("PADDLE_KV_RETRIES", "3"))
    delay = 0.05
    for attempt in range(retries + 1):
        try:
            _faults.kv_fault(method)       # deterministic injection point
            return getattr(client, method)(*args)
        except Exception as e:                             # noqa: BLE001
            if _is_deadline(e) or not _is_transient(e) \
                    or attempt >= retries:
                raise
            _watchdog_stats["kv_retries"] += 1
            time.sleep(delay)
            delay *= 2


def _watchdog_detail(e):
    """(convert?, detail) for an exception escaping a rendezvous _kv_call:
    deadlines and retry-exhausted transients both become
    CollectiveTimeout — the group is equally broken either way, and the
    operator needs op/group/ranks, not a bare KV stack."""
    if _is_deadline(e):
        return True, str(e).splitlines()[0]
    if _is_transient(e):
        return True, ("PADDLE_KV_RETRIES exhausted on a transient "
                      "coordinator failure: " + str(e).splitlines()[0])
    return False, None


def _kv_world():
    """(client, process_count, process_index) — one seam for the
    watchdog unit tests to stand in a fake coordination service."""
    from jax._src import distributed
    return distributed.global_state.client, jax.process_count(), \
        jax.process_index()


def _ranks_seen(client, key, n, budget_s=5.0):
    """Post-timeout forensics: which ranks' contributions exist in the
    store?  Direct client calls (no retry backoff) with a tiny per-key
    deadline AND a total time budget — on a big pod the diagnosis must
    cost seconds, not minutes; ranks not probed before the budget ran
    out simply don't appear."""
    seen = []
    deadline = time.monotonic() + budget_s
    for j in range(n):
        if time.monotonic() > deadline:
            break
        try:
            client.blocking_key_value_get(f"{key}/{j}", 200)
            seen.append(j)
        except Exception:                                  # noqa: BLE001
            pass
    return seen


def _kv_allgather(value, op="allgather", bucket=None, group=None):
    """Host allgather over the jax.distributed coordination service's
    key-value store — no XLA collective involved, so it works on backends
    whose device collectives can't span processes (CPU).  Strictly
    control-plane: payloads ride the coordinator, so keep them small.

    Watchdog: every wait is bounded by PADDLE_COLLECTIVE_TIMEOUT; an
    expired rendezvous raises CollectiveTimeout naming the op, group,
    bucket, and the ranks whose contributions DID arrive, instead of
    hanging the training loop forever."""
    import base64
    import pickle
    client, n, me = _kv_world()
    timeout_ms = _collective_timeout_ms()
    _kv_seq[0] += 1
    key = f"paddle_tpu_eager_ag_{_kv_seq[0]}"
    payload = base64.b64encode(
        pickle.dumps(np.asarray(value))).decode("ascii")
    _kv_call(client, "key_value_set", f"{key}/{me}", payload)
    # rendezvous wait, measured AFTER this rank contributed: a straggler
    # (slow producer) records ~zero here while its peers record the time
    # they sat at the barrier — the asymmetry the telemetry aggregator's
    # straggler detector keys on (observability/aggregate.py)
    t_wait = time.perf_counter()
    try:
        _kv_call(client, "wait_at_barrier", f"{key}_barrier", timeout_ms)
        rows = [pickle.loads(base64.b64decode(_kv_call(
            client, "blocking_key_value_get", f"{key}/{j}", timeout_ms))) for j in range(n)]
        _timeline.record_collective_wait(
            time.perf_counter() - t_wait, op=op)
    except Exception as e:                                 # noqa: BLE001
        convert, detail = _watchdog_detail(e)
        if not convert:
            raise
        _watchdog_stats["collective_timeouts"] += 1
        raise CollectiveTimeout(
            op, timeout_ms, group=group, bucket=bucket,
            ranks_seen=_ranks_seen(client, key, n), nranks=n,
            detail=detail) from e
    # everyone has read every row — each process reclaims its own key so
    # per-step collectives don't grow the coordinator's store unboundedly
    try:
        _kv_call(client, "wait_at_barrier", f"{key}_drain", timeout_ms)
    except Exception as e:                                 # noqa: BLE001
        convert, detail = _watchdog_detail(e)
        if not convert:
            raise
        # a peer vanished AFTER contributing: the gather completed but
        # the group is broken — same diagnosable failure, named as such
        _watchdog_stats["collective_timeouts"] += 1
        raise CollectiveTimeout(
            op, timeout_ms, group=group, bucket=bucket,
            ranks_seen=_ranks_seen(client, key, n), nranks=n,
            detail="post-gather drain barrier: " + detail) from e
    try:
        client.key_value_delete(f"{key}/{me}")
    except Exception:                                      # noqa: BLE001
        pass                       # older client without delete: best effort
    return np.stack(rows)


def _eager_rows(value, op="allgather", bucket=None, group=None):
    """Host-level cross-process allgather: every live process contributes
    its local value; returns a [process_count, ...] numpy stack."""
    from jax.experimental import multihost_utils
    if _faults.active():
        _faults.collective_entry(op)       # injected straggler/vanish
    # where the backend gathers across processes itself, the rendezvous
    # happens inside this one call: timed from entry, a straggler (late
    # to enter) records ~zero and its peers the time they sat waiting —
    # the same asymmetry _kv_allgather records for the fallback
    t_wait = time.perf_counter()
    try:
        rows = np.asarray(
            multihost_utils.process_allgather(np.asarray(value)))
    except CollectiveTimeout:
        raise
    except Exception:                                      # noqa: BLE001
        # e.g. "Multiprocess computations aren't implemented on the CPU
        # backend" — gather through the coordination service instead
        return _kv_allgather(value, op=op, bucket=bucket, group=group)
    _timeline.record_collective_wait(time.perf_counter() - t_wait, op=op)
    return rows


def _member_rows(rows, group):
    """(member?, member rows) for a possibly-subset group."""
    if (group is not None and group.ranks
            and len(group.ranks) < rows.shape[0]):
        return group.rank >= 0, rows[np.asarray(group.ranks)]
    return True, rows


def _adopt(tensor, value):
    """Rebind ``tensor`` to a host value, preserving trainability (a bare
    Tensor defaults to stop_gradient=True — adopting that would silently
    freeze a Parameter)."""
    sg = tensor.stop_gradient
    tensor._rebind(Tensor(value))
    tensor.stop_gradient = sg
    return tensor


def _get_global_group():
    global _default_group
    if _default_group is None:
        from .parallel import get_rank, get_world_size
        _default_group = Group(get_rank(), max(get_world_size(), 1), 0)
    return _default_group


def get_group(gid=0):
    if gid == 0:
        return _get_global_group()
    return _group_map.get(gid)


def new_group(ranks=None, backend=None, axis_name=None):
    from .parallel import get_rank
    gid = len(_group_map) + 1
    ranks = ranks or []
    me = get_rank()
    rank = ranks.index(me) if me in ranks else (0 if not ranks else -1)
    g = Group(rank, max(len(ranks), 1), gid, ranks, axis_name)
    _group_map[gid] = g
    return g


_barrier_counter = [0]


def barrier(group=None):
    if _process_count() > 1:
        from jax.experimental import multihost_utils
        _barrier_counter[0] += 1
        name = f"paddle_tpu_barrier_{_barrier_counter[0]}"
        timeout_ms = _collective_timeout_ms()
        try:
            multihost_utils.sync_global_devices(name)
        except Exception:                                  # noqa: BLE001
            # CPU backend: no cross-process device collectives — use the
            # coordination service barrier directly (watchdog-bounded)
            client, n, _ = _kv_world()
            try:
                _kv_call(client, "wait_at_barrier", name, timeout_ms)
            except Exception as e:                         # noqa: BLE001
                convert, detail = _watchdog_detail(e)
                if not convert:
                    raise
                _watchdog_stats["collective_timeouts"] += 1
                raise CollectiveTimeout(
                    "barrier", timeout_ms, group=group, nranks=n,
                    detail=detail) from e
        return
    jnp.zeros(()).block_until_ready()


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor) and hasattr(tensor.value,
                                              "block_until_ready"):
        tensor.value.block_until_ready()


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    ax = _current_axis(group)
    if ax is None:
        if _process_count() > 1:
            member, rows = _member_rows(_eager_rows(
                tensor.numpy(), op="all_reduce", group=group), group)
            if not member:
                return tensor
            red = {ReduceOp.SUM: rows.sum(0), ReduceOp.MAX: rows.max(0),
                   ReduceOp.MIN: rows.min(0), ReduceOp.PROD: rows.prod(0),
                   ReduceOp.AVG: rows.mean(0)}[op]
            _adopt(tensor, red.astype(rows.dtype))
            return tensor
        return tensor  # world of one: identity

    def _ar(x):
        if op == ReduceOp.SUM:
            return jax.lax.psum(x, ax)
        if op == ReduceOp.MAX:
            return jax.lax.pmax(x, ax)
        if op == ReduceOp.MIN:
            return jax.lax.pmin(x, ax)
        if op == ReduceOp.AVG:
            return jax.lax.pmean(x, ax)
        if op == ReduceOp.PROD:
            return jnp.exp(jax.lax.psum(jnp.log(x), ax))
        raise ValueError(op)
    out = call(_ar, tensor, _name="c_allreduce")
    tensor._rebind(out)
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # XLA collectives are symmetric; reduce == all_reduce with only dst using
    # the value (the compiler DCEs unused outputs elsewhere)
    return all_reduce(tensor, op, group, sync_op)


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    ax = _current_axis(group)
    if ax is None:
        if _process_count() > 1:
            member, rows = _member_rows(_eager_rows(
                tensor.numpy(), op="all_gather", group=group), group)
            if member:
                tensor_list.extend(Tensor(r) for r in rows)
            return tensor_list
        tensor_list.append(tensor.clone())
        return tensor_list

    def _ag(x):
        return jax.lax.all_gather(x, ax)
    gathered = call(_ag, tensor, _name="c_allgather")
    n = gathered.shape[0]
    from ..tensor.manipulation import unstack
    tensor_list.extend(unstack(gathered, axis=0, num=n))
    return tensor_list


def all_gather_object(obj_list, obj, group=None):
    if _process_count() > 1:
        import pickle
        buf = np.frombuffer(pickle.dumps(obj), np.uint8)
        # two rounds: agree on the max payload size, then gather padded
        sizes = _eager_rows(np.asarray([buf.size], np.int64),
                            op="all_gather_object", group=group)[:, 0]
        padded = np.zeros(int(sizes.max()), np.uint8)
        padded[:buf.size] = buf
        rows = _eager_rows(padded, op="all_gather_object", group=group)
        member, rows = _member_rows(rows, group)
        if member:
            msizes = _member_rows(sizes[:, None], group)[1][:, 0]
            obj_list.extend(pickle.loads(r[:int(n)].tobytes())
                            for r, n in zip(rows, msizes))
        return obj_list
    obj_list.append(obj)
    return obj_list


def broadcast(tensor, src=0, group=None, sync_op=True):
    ax = _current_axis(group)
    if ax is None:
        if _process_count() > 1:
            # src is a GLOBAL rank (reference semantics): gather
            # unfiltered; only group MEMBERS adopt src's row
            rows = _eager_rows(tensor.numpy(), op="broadcast",
                               group=group)
            if group is None or not group.ranks \
                    or len(group.ranks) >= rows.shape[0] \
                    or group.rank >= 0:
                _adopt(tensor, rows[src])
            return tensor
        return tensor

    def _bc(x):
        # take src's value on every member: gather then index
        return jax.lax.all_gather(x, ax)[src]
    out = call(_bc, tensor, _name="c_broadcast")
    tensor._rebind(out)
    return tensor


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    ax = _current_axis(group)
    if ax is None:
        if _process_count() > 1:
            # every process must contribute the SAME shape: n_slots is
            # the group size (the number of scatter destinations), and
            # each member's slot is its group rank
            n_slots = (len(group.ranks)
                       if group is not None and group.ranks
                       and len(group.ranks) < _process_count()
                       else _process_count())
            me = jax.process_index()
            if (group is not None and group.ranks
                    and len(group.ranks) < _process_count()):
                if group.rank < 0:
                    _eager_rows(np.zeros(
                        (n_slots,) + tuple(np.asarray(
                            tensor.numpy()).shape),
                        np.asarray(tensor.numpy()).dtype))
                    return tensor     # non-member: participate, no adopt
                me = group.rank
            if tensor_list:
                local = np.stack([np.asarray(t.numpy())
                                  for t in tensor_list])
            else:
                local = np.zeros(
                    (n_slots,) + tuple(np.asarray(tensor.numpy()).shape),
                    np.asarray(tensor.numpy()).dtype)
            rows = _eager_rows(local, op="scatter",
                               group=group)   # [nproc, n_slots, ...]
            _adopt(tensor, rows[src, me])
            return tensor
        if tensor_list:
            tensor._rebind(tensor_list[0].clone())
        return tensor
    from ..tensor.manipulation import stack

    def _sc(stacked):
        idx = jax.lax.axis_index(ax)
        return jnp.take(jax.lax.all_gather(stacked, ax)[src], idx, axis=0)
    out = call(_sc, stack(tensor_list, 0), _name="c_scatter")
    tensor._rebind(out)
    return tensor


def alltoall(in_tensor_list, out_tensor_list, group=None, sync_op=True):
    ax = _current_axis(group)
    if ax is None:
        if _process_count() > 1:
            # subset groups map through group ranks exactly like scatter:
            # slots are GROUP ranks, non-members feed the global gather a
            # zero payload and adopt nothing
            subset = (group is not None and group.ranks
                      and len(group.ranks) < _process_count())
            if subset:
                n_slots = len(group.ranks)
                if group.rank < 0:
                    sample = np.asarray(in_tensor_list[0].numpy())
                    _eager_rows(np.zeros((n_slots,) + sample.shape,
                                         sample.dtype))
                    return out_tensor_list  # non-member: participate only
                me = group.rank
            else:
                me = jax.process_index()
            local = np.stack([np.asarray(t.numpy())
                              for t in in_tensor_list])
            rows = _eager_rows(local, op="alltoall",
                               group=group)   # [nproc, n_slots, ...]
            member, rows = _member_rows(rows, group)
            # group-member j's slot-`me` entry is my j-th output
            out_tensor_list.extend(Tensor(rows[j, me])
                                   for j in range(rows.shape[0]))
            return out_tensor_list
        out_tensor_list.extend(t.clone() for t in in_tensor_list)
        return out_tensor_list
    from ..tensor.manipulation import stack, unstack

    def _a2a(x):
        return jax.lax.all_to_all(x, ax, split_axis=0, concat_axis=0,
                                  tiled=False)
    stacked = stack(in_tensor_list, 0)
    out = call(_a2a, stacked, _name="c_alltoall")
    out_tensor_list.extend(unstack(out, axis=0, num=len(in_tensor_list)))
    return out_tensor_list


def send(tensor, dst=0, group=None, sync_op=True):
    ax = _current_axis(group)
    if ax is None:
        _p2p_buf.append(tensor.clone())
        return

    # a single-program SPMD region cannot express "whoever calls send
    # owns the payload" — a ppermute with every source targeting dst is
    # an invalid collective (duplicate destinations).  Point-to-point
    # inside mapped code is spelled as an explicit shift/permutation
    # (jax.lax.ppermute), which the pipeline/ring APIs already use.
    raise NotImplementedError(
        "send() inside a mapped region has no SPMD meaning; use "
        "jax.lax.ppermute with an explicit (src, dst) permutation (see "
        "parallel/pipeline.py) or the eager cross-process collectives")


_p2p_buf = []


def recv(tensor, src=0, group=None, sync_op=True):
    ax = _current_axis(group)
    if ax is None:
        if _p2p_buf:
            tensor._rebind(_p2p_buf.pop(0))
        return tensor

    raise NotImplementedError(
        "recv() inside a mapped region has no SPMD meaning; use "
        "jax.lax.ppermute with an explicit (src, dst) permutation (see "
        "parallel/pipeline.py) or the eager cross-process collectives")


def _c_identity(tensor, group=None):
    return tensor


def _c_concat(tensor, group=None):
    ax = _current_axis(group)
    if ax is None:
        return tensor

    def _cc(x):
        return jax.lax.all_gather(x, ax, axis=x.ndim - 1, tiled=True)
    return call(_cc, tensor, _name="c_concat")


def _c_split(tensor, group=None):
    ax = _current_axis(group)
    if ax is None:
        return tensor

    def _cs(x):
        idx = jax.lax.axis_index(ax)
        from ..framework.jax_compat import axis_size
        n = axis_size(ax)
        sz = x.shape[-1] // n
        return jax.lax.dynamic_slice_in_dim(x, idx * sz, sz, axis=x.ndim - 1)
    return call(_cs, tensor, _name="c_split")


def _mp_allreduce(tensor, group=None):
    return all_reduce(tensor, ReduceOp.SUM, group)


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    """With ``tensor_list`` (the reference contract), each rank
    contributes its list and receives the reduction of everyone's
    rank-th entry into ``tensor``; without it, ``tensor`` itself is
    reduced and scattered along axis 0."""
    from ..tensor.manipulation import stack
    src = stack(tensor_list, 0) if tensor_list else tensor
    ax = _current_axis(group)
    if ax is None:
        if _process_count() > 1:
            member, rows = _member_rows(_eager_rows(
                src.numpy(), op="reduce_scatter", group=group), group)
            if member:
                red = {ReduceOp.SUM: rows.sum(0),
                       ReduceOp.AVG: rows.mean(0),
                       ReduceOp.MAX: rows.max(0),
                       ReduceOp.MIN: rows.min(0),
                       ReduceOp.PROD: rows.prod(0)}[op]
                n = rows.shape[0]
                me = jax.process_index()
                if group is not None and group.ranks and n < _process_count():
                    me = group.rank           # subset group: scatter by
                if tensor_list:               # group rank, not global
                    _adopt(tensor, red[me])   # slot per rank, no extra dim
                else:
                    sz = red.shape[0] // n
                    _adopt(tensor, red[me * sz:(me + 1) * sz])
            return tensor
        if tensor_list:
            _adopt(tensor, src.numpy()[0])    # world of one: first slot
        return tensor

    def _rs(x):
        from ..framework.jax_compat import psum_scatter
        return psum_scatter(x, ax, scatter_dimension=0,
                            tiled=not bool(tensor_list))
    out = call(_rs, src, _name="c_reduce_scatter")
    tensor._rebind(out)
    return tensor


def split(x, num_or_sections, axis=0):
    from ..tensor.manipulation import split as _split
    return _split(x, num_or_sections, axis)
