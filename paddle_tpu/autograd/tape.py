"""Eager autograd engine: a define-by-run tape over ``jax.vjp``.

TPU-native replacement for the reference's C++ imperative engine
(ref: paddle/fluid/imperative/tracer.cc, basic_engine.cc).  The reference
records OpBase nodes with per-op GradOpMaker kernels; we record one tape node
per dispatched primitive holding the ``jax.vjp`` closure, so every op's
gradient comes from XLA-differentiated code instead of hand-written grad
kernels.  Under ``jit.to_static`` the tape is bypassed entirely and
``jax.grad`` differentiates the whole step.
"""
from __future__ import annotations

import contextlib
from typing import List, Optional, Sequence

import jax.numpy as jnp

from ..framework import core


class Node:
    """One recorded primitive application."""

    __slots__ = ("vjp_fn", "parents", "parent_links", "n_outputs",
                 "out_shapes", "out_dtypes", "_accum", "name", "out_hooks",
                 "fwd_closure")

    def __init__(self, vjp_fn, parents, n_outputs, out_shapes, out_dtypes,
                 name=""):
        self.vjp_fn = vjp_fn
        self.parents = parents        # list[Tensor] — diff inputs only
        # SNAPSHOT each parent's producing (node, output index) at record
        # time: an in-place op later REBINDS the tensor object onto its
        # own new node, and resolving parents through the live tensor
        # would then seed the cotangent into that new node (a self-loop),
        # silently severing every upstream gradient
        self.parent_links = [(getattr(p, "_node", None),
                              getattr(p, "_node_index", 0))
                             for p in parents]
        self.n_outputs = n_outputs
        self.out_shapes = out_shapes
        self.out_dtypes = out_dtypes
        self._accum: Optional[list] = None
        self.name = name
        self.out_hooks = None         # {out_index: hook list} (register_hook
                                      # on a non-leaf tensor)
        self.fwd_closure = None       # pure fn(*parent_vals) -> out(s), for
                                      # create_graph double-backward

    def seed(self, index: int, grad):
        if self._accum is None:
            self._accum = [None] * self.n_outputs
        if self._accum[index] is None:
            self._accum[index] = grad
        else:
            self._accum[index] = self._accum[index] + grad

    def cotangents(self):
        import numpy as np
        import jax
        out = []
        for i in range(self.n_outputs):
            g = self._accum[i] if self._accum else None
            if g is None:
                dt = self.out_dtypes[i]
                if jnp.issubdtype(dt, jnp.inexact):
                    g = jnp.zeros(self.out_shapes[i], dt)
                else:
                    # non-differentiable outputs take float0 cotangents
                    g = np.zeros(self.out_shapes[i], jax.dtypes.float0)
            out.append(g)
        return tuple(out)


class NoGrad:
    """Context manager / decorator disabling tape recording (paddle.no_grad)."""

    def __init__(self):
        self._prev = None

    def __enter__(self):
        self._prev = core.grad_enabled()
        core.set_grad_enabled_flag(False)
        return self

    def __exit__(self, *exc):
        core.set_grad_enabled_flag(self._prev)
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*a, **k):
            with NoGrad():
                return fn(*a, **k)
        return wrapper


no_grad = NoGrad


class enable_grad:
    def __init__(self):
        self._prev = None

    def __enter__(self):
        self._prev = core.grad_enabled()
        core.set_grad_enabled_flag(True)
        return self

    def __exit__(self, *exc):
        core.set_grad_enabled_flag(self._prev)
        return False


class set_grad_enabled:
    def __init__(self, mode: bool):
        self._mode = bool(mode)
        self._prev = core.grad_enabled()
        core.set_grad_enabled_flag(self._mode)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        core.set_grad_enabled_flag(self._prev)
        return False


def is_grad_enabled() -> bool:
    return core.grad_enabled()


def _topo_order(root_node) -> List[Node]:
    """Post-order DFS over the node DAG (iterative; graphs can be deep)."""
    order: List[Node] = []
    visited = set()
    stack = [(root_node, False)]
    while stack:
        node, processed = stack.pop()
        if processed:
            order.append(node)
            continue
        if id(node) in visited:
            continue
        visited.add(id(node))
        stack.append((node, True))
        for pn, _ in node.parent_links:
            if pn is not None and id(pn) not in visited:
                stack.append((pn, False))
    return order  # post-order: parents before children; reverse for backward


def apply_grad_hooks(hooks, g):
    """Fire grad hooks over raw value ``g`` (snapshot: a hook removing
    itself must not skip its neighbor); non-None returns rewrite."""
    from ..tensor import Tensor

    for hook in tuple(hooks):
        out = hook(Tensor(g))
        if out is not None:
            g = out.value if isinstance(out, Tensor) else out
    return g


# Callbacks queued DURING a backward pass (e.g. by grad-ready hooks) that
# must run once the pass completes — the reducer's "finalize buckets at
# end of backward" plumbing (ref: the NCCL reducer's
# queue_callback/finalize_backward pair in imperative/reducer.cc).  The
# queue is drained after leaf grads finalize; on an aborted backward it is
# cleared WITHOUT running, so a stale finalize can't fire mid-way through
# the next pass.
_backward_end_callbacks: List = []

# depth of in-flight watch-mode (paddle.grad) reverse passes: grad-ready
# consumers like the DataParallel reducer must NOT treat a functional
# gradient query as a training backward (its hooks fire only for watched
# tensors, and a bucket finalize would zero-fill every other member)
_watch_depth = [0]

# total backward nesting depth (a grad hook may itself run paddle.grad /
# backward): end-of-backward callbacks drain only when the OUTERMOST pass
# finishes — an inner pass draining the outer pass's queued reducer
# finalize would reduce half-filled buckets mid-walk
_backward_depth = [0]


def in_watch_backward() -> bool:
    return _watch_depth[0] > 0


def queue_backward_end_callback(fn):
    _backward_end_callbacks.append(fn)


def _drain_backward_end_callbacks(run):
    try:
        if run:
            while _backward_end_callbacks:
                _backward_end_callbacks.pop(0)()
    finally:
        del _backward_end_callbacks[:]


def backward(tensor, grad=None, retain_graph: bool = False, watch=()):
    """Run reverse-mode accumulation from ``tensor`` into leaf ``.grad``s.

    ``watch``: ids of non-leaf tensors that should ALSO accumulate ``.grad``
    (used by paddle.grad to differentiate w.r.t. intermediates)."""
    if watch:
        _watch_depth[0] += 1
    _backward_depth[0] += 1
    # telemetry: only the OUTERMOST training backward is a "backward"
    # phase (nested/double-grad passes ride inside it, and watch-mode
    # passes are functional gradient queries, not training steps)
    from ..observability import timeline as _timeline
    _span = (_timeline.span("backward")
             if _backward_depth[0] == 1 and not watch
             else contextlib.nullcontext())
    try:
        with _span:
            _backward_impl(tensor, grad, retain_graph, watch)
    except BaseException:
        # an aborted OUTERMOST pass must not leave finalize callbacks
        # queued for the NEXT backward (they would fire over
        # half-accumulated buckets); an inner pass leaves the outer
        # pass's queue alone — the outer except will deal with it
        if _backward_depth[0] == 1:
            _drain_backward_end_callbacks(run=False)
        raise
    finally:
        _backward_depth[0] -= 1
        if watch:
            _watch_depth[0] -= 1
    if _backward_depth[0] == 0:
        _drain_backward_end_callbacks(run=True)


def _backward_impl(tensor, grad, retain_graph, watch):
    from ..tensor import Tensor

    if tensor._node is None:
        if tensor.stop_gradient:
            raise RuntimeError(
                "Tensor.backward() called on a tensor with stop_gradient=True "
                "and no graph")
        return
    if grad is None:
        grad = jnp.ones(tensor.shape, tensor.dtype)
    elif isinstance(grad, Tensor):
        grad = grad.value

    # buffer per-tensor contributions so grad hooks fire exactly once with
    # the completed grad of this backward pass (ref VarBase hook semantics);
    # entries are (tensor, grad, hooks_done)
    pending = {}

    def _add(t, g):
        ent = pending.get(id(t))
        pending[id(t)] = (t, g if ent is None else ent[1] + g, False)

    if watch and id(tensor) in watch:
        _add(tensor, grad)

    root = tensor._node
    root.seed(tensor._node_index, grad)

    order = _topo_order(root)
    # Per-leaf contribution counts: a leaf's grad is COMPLETE the moment
    # the last node referencing it has run its vjp — firing its hooks
    # right there (instead of after the whole walk) lets grad-ready hooks
    # (DataParallel's bucketed reducer) launch collectives asynchronously
    # while backward is still tracing earlier layers.
    leaf_remaining: dict = {}
    if not watch:
        for node in order:
            for parent, (pn, _) in zip(node.parents, node.parent_links):
                if pn is None:
                    leaf_remaining[id(parent)] = \
                        leaf_remaining.get(id(parent), 0) + 1
    for node in reversed(order):
        if node.vjp_fn is None:
            raise RuntimeError(
                "Trying to backward through the graph a second time. "
                "Pass retain_graph=True to the first .backward() if you "
                "need to backward twice.")
        cts = node.cotangents()
        if node.out_hooks:
            # register_hook on a non-leaf: its complete grad is this
            # output's cotangent — fire once, apply rewrites; if the tensor
            # is also watched (paddle.grad input), its accumulated grad is
            # exactly this rewritten cotangent, with hooks already done
            cts = list(cts)
            for idx, (hooks, tref) in node.out_hooks.items():
                g = apply_grad_hooks(hooks, cts[idx])
                cts[idx] = g
                t = tref()
                if t is not None and watch and id(t) in watch:
                    pending[id(t)] = (t, g, True)
        if node.n_outputs == 1:
            in_grads = node.vjp_fn(cts[0])
        else:
            in_grads = node.vjp_fn(cts)
        for parent, (pn, pidx), g in zip(node.parents, node.parent_links,
                                         in_grads):
            if g is not None:
                if watch:
                    # paddle.grad mode: accumulate ONLY into requested
                    # tensors
                    if id(parent) in watch:
                        _add(parent, g)
                    if pn is not None:
                        pn.seed(pidx, g)
                elif pn is not None:
                    pn.seed(pidx, g)
                else:
                    _add(parent, g)
            if pn is None and not watch:
                # one contribution edge consumed (g None counts too: that
                # edge will never contribute); at zero the leaf's grad is
                # final for this pass — fire its hooks NOW, mid-walk
                rem = leaf_remaining[id(parent)] = \
                    leaf_remaining[id(parent)] - 1
                if rem == 0:
                    ent = pending.pop(id(parent), None)
                    if ent is not None:
                        ent[0]._finalize_grad(ent[1])
        node._accum = None
        if not retain_graph:
            node.vjp_fn = None
            node.fwd_closure = None   # frees captured forward arrays too
    for t, g, hooks_done in pending.values():
        if hooks_done:
            t._accumulate_grad(g)
        else:
            t._finalize_grad(g)
    # explicit "backward already ran from this root" stamp: minimize()
    # consults it instead of inferring from vjp_fn liveness, which a
    # retain_graph=True backward keeps alive (grads would double)
    tensor._backward_ran = True
    if not retain_graph:
        # break links so the graph is freed and cannot be reused
        for node in order:
            node.parents = ()
            node.parent_links = ()


def _backward_create_graph(tensor, grad, watch):
    """Reverse pass whose every vjp application is itself dispatched and
    tape-recorded, so the returned grads carry a live graph (double
    backward).  Each node's vjp is REBUILT from its forward closure with
    the parent tensors as differentiable inputs — the second derivative
    therefore sees the primal dependence of the first (ref dygraph
    double-grad: python/paddle/fluid/imperative/partial_grad_engine.cc).
    Returns {id(watched tensor): grad Tensor}."""
    import numpy as np
    import jax
    from ..tensor import Tensor
    from ..ops import dispatch

    root = tensor._node
    # per-node output cotangent Tensors
    acc: dict = {}

    def seed(node, idx, g):
        key = (id(node), idx)
        acc[key] = g if key not in acc else acc[key] + g

    out_grads: dict = {}

    def add_out(t, g):
        out_grads[id(t)] = g if id(t) not in out_grads else \
            out_grads[id(t)] + g

    g0 = grad if isinstance(grad, Tensor) else Tensor(grad)
    if id(tensor) in watch:
        add_out(tensor, g0)
    seed(root, tensor._node_index, g0)

    for node in reversed(_topo_order(root)):
        if node.fwd_closure is None:
            raise RuntimeError(
                f"create_graph=True cannot differentiate through node "
                f"'{node.name}': no forward closure available (the graph "
                "was freed by a backward() without retain_graph, or the "
                "node is a PyLayer — custom PyLayers do not support "
                "eager double-grad)")
        inexact = [i for i in range(node.n_outputs)
                   if jnp.issubdtype(node.out_dtypes[i], jnp.inexact)]
        cts = []
        for i in inexact:
            g = acc.get((id(node), i))
            if g is None:
                g = Tensor(jnp.zeros(node.out_shapes[i],
                                     node.out_dtypes[i]))
            cts.append(g)
        if node.out_hooks:
            # honor register_hook rewrites, same as the plain backward
            from ..tensor import Tensor as _T
            for pos, i in enumerate(inexact):
                ent = node.out_hooks.get(i)
                if ent:
                    g = cts[pos]
                    for hook in tuple(ent[0]):
                        out = hook(g if isinstance(g, _T) else _T(g))
                        if out is not None:
                            g = out
                    cts[pos] = g
        n_ct = len(cts)
        closure = node.fwd_closure
        n_out = node.n_outputs
        shapes = node.out_shapes
        inexact_t = tuple(inexact)

        def vjp_op(*vals, _closure=closure, _n_ct=n_ct, _n_out=n_out,
                   _shapes=shapes, _inexact=inexact_t):
            ct_vals, parent_vals = vals[:_n_ct], vals[_n_ct:]
            _, vjp_fn = jax.vjp(_closure, *parent_vals)
            full = []
            k = 0
            for i in range(_n_out):
                if i in _inexact:
                    full.append(ct_vals[k])
                    k += 1
                else:
                    full.append(np.zeros(_shapes[i], jax.dtypes.float0))
            ct = full[0] if _n_out == 1 else tuple(full)
            gs = vjp_fn(ct)
            return tuple(gs) if len(gs) > 1 else gs[0]

        grads = dispatch.call(vjp_op, *cts, *node.parents,
                              _name=f"grad_{node.name}")
        if not isinstance(grads, tuple):
            grads = (grads,)
        for parent, (p_n, p_i), g in zip(node.parents, node.parent_links,
                                         grads):
            if id(parent) in watch:
                add_out(parent, g)
            if p_n is not None:
                seed(p_n, p_i, g)
    return out_grads


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None):
    """paddle.grad: functional gradient of outputs wrt inputs (eager tape).

    ref: python/paddle/fluid/dygraph/base.py::grad.  With
    ``create_graph=True`` the reverse pass is itself recorded on the tape
    (each vjp rebuilt from its forward closure), so the results support a
    further backward — gradient penalties work in pure eager mode.
    """
    from ..tensor import Tensor

    if isinstance(outputs, Tensor):
        outputs = [outputs]
    if isinstance(inputs, Tensor):
        inputs = [inputs]
    if grad_outputs is None:
        grad_outputs = [None] * len(outputs)
    elif isinstance(grad_outputs, Tensor):
        grad_outputs = [grad_outputs]

    watch = {id(t) for t in inputs}

    if create_graph:
        merged: dict = {}
        for o, go in zip(outputs, grad_outputs):
            if o._node is None:
                continue
            g0 = go if go is not None else Tensor(jnp.ones(o.shape, o.dtype))
            for tid, gt in _backward_create_graph(o, g0, watch).items():
                merged[tid] = gt if tid not in merged else merged[tid] + gt
        results = []
        for t in inputs:
            g = merged.get(id(t))
            if g is None and not allow_unused:
                raise RuntimeError(
                    "paddle.grad: one of the inputs is unused in the "
                    "graph of outputs (no gradient path); pass "
                    "allow_unused=True to get None for it instead")
            results.append(g)
        return results

    # save/restore existing leaf grads: paddle.grad must not touch .grad
    saved = [t._grad for t in inputs]
    for t in inputs:
        t._grad = None
    retain = True if retain_graph is None else retain_graph
    try:
        for o, go in zip(outputs, grad_outputs):
            backward(o, go, retain_graph=retain, watch=watch)
        results = []
        for t, s in zip(inputs, saved):
            g = t._grad
            if g is None and not allow_unused:
                raise RuntimeError(
                    "paddle.grad: one of the inputs is unused in the "
                    "graph of outputs (no gradient path); pass "
                    "allow_unused=True to get None for it instead")
            results.append(Tensor(g) if g is not None else None)
    finally:
        for t, s in zip(inputs, saved):
            t._grad = s
    return results
