"""Graph-rewrite optimizer for captured lazy segments.

The lazy engine (graph.py) compiles the recorded dataflow segment exactly
as captured; this module is the pass that *rewrites* that graph first —
the TVM rule-driven operator-fusion idea (arXiv:1802.04799) applied to
the segment the compile-once discipline (arXiv:2603.09555) already
amortizes: every rewrite is paid once per distinct segment signature and
replayed for free on every warm flush.

Pipeline position: AFTER weakref-liveness DCE and the stable renumbering
(the rewriter consumes the renumbered ``(specs, leaf_avals, out_spec)``
signature, never raw nodes), BEFORE the jitted flush compile. A rewritten
segment enters ``CompileCache("lazy")`` under a ``("rw", ...)`` key built
from the POST-rewrite signature plus the rule configuration, so rewritten
and unrewritten programs can never collide — and a config flip (per-rule
gate, spmd mesh change) keys a fresh executable instead of silently
reusing a stale one.

Three rule families, each individually disableable via
``MXNET_LAZY_REWRITE_DISABLE`` (comma-separated rule names):

* algebraic/fusion — ``identity`` (add-of-zeros / mul-by-one /
  double-negation / transpose-of-transpose / identity-op elimination),
  ``cse`` (dedup of identical (op, attrs, inputs) nodes),
  ``dense_bias_act`` (dot + bias-add + relu collapse — the fused op
  re-invokes the SAME registered fns, so the trace is bit-identical),
  ``conv_bn_relu`` (Convolution + eval-mode BatchNorm (+ relu) into the
  serving fusion kernel ``_fused_conv_bn_relu`` — generalizes the
  symbol-level ``TPU_FUSE`` pass to every lazy region; BN folding
  reorders float math, so parity is ulp-level, the PR 6 FMA precedent),
  ``map_reduce`` (a dead unary elementwise chain feeding a reduction
  merges into one ``_rw_map_reduce`` node).
* sharding-aware — ``spmd_constraint``: when ``MXNET_SPMD`` is gated,
  inject ``sharding_constraint`` nodes at large segment leaves using the
  PR 14 planner's residency mode (shape-only — lazy leaves are
  anonymous), so imperative op-by-op code inherits the 1/N layouts the
  fused step already gets. On a trivial (single-device / tp=1) mesh the
  constraint is a pure layout annotation and lowers to ZERO collectives
  (pinned by test_lazy_rewrite + the hlolint ``lazy`` contract row).

Vjp nodes are never rewritten (their residual pytree structure is pinned
by ``_LazyVjp``); they only *consume* rewritten forward values, which is
how autograd captured inside a segment sees the rewritten forward.

The rewrite PLAN is memoized per (pre-rewrite signature, config token):
a steady-state flush pays one dict hit, preserving the lazy lane's
host-dispatch win. Rule metadata lives in :data:`RULES` — the one
registry the symbol-level fusion pass (symbol/fusion.py) shares via
:func:`fused_conv_bn_attrs`.
"""
from __future__ import annotations

import collections
import functools

from .. import analysis
from .. import telemetry

__all__ = ["enabled", "disabled_rules", "plan_for", "note_applied",
           "RULES", "rule_names", "fused_conv_bn_attrs", "config_token"]


# ---------------------------------------------------------------------------
# rule registry — shared metadata for the lazy rewriter AND the symbol-level
# fusion pass (symbol/fusion.py tags its TPU_FUSE property as the "symbol"
# implementation of conv_bn_relu; docs/faq/env_var.md lists these names as
# the MXNET_LAZY_REWRITE_DISABLE vocabulary)
# ---------------------------------------------------------------------------

class Rule:
    __slots__ = ("name", "family", "doc", "levels", "parity")

    def __init__(self, name, family, doc, levels=("lazy",), parity="bit"):
        self.name = name
        self.family = family
        self.doc = doc
        self.levels = tuple(levels)   # where implementations exist
        self.parity = parity          # "bit" | "ulp" vs the unrewritten replay


RULES = collections.OrderedDict()


def _rule(name, family, doc, levels=("lazy",), parity="bit"):
    RULES[name] = Rule(name, family, doc, levels, parity)


_rule("identity", "algebraic",
      "drop add-of-_zeros / mul-by-_ones / sub-of-_zeros nodes (shape and "
      "dtype proven equal from avals), scalar +0/*1/div-1, double "
      "negation, transpose-of-transpose composing to the identity "
      "permutation, and the identity op")
_rule("cse", "algebraic",
      "merge nodes with identical (op, attrs, kind='op', inputs); "
      "duplicated LIVE outputs collapse to one program output")
_rule("dense_bias_act", "fusion",
      "dot -> (broadcast|elemwise)_add bias -> relu/Activation(relu) "
      "collapses to _rw_dense_bias_act (re-invokes the same registered "
      "fns: bit-identical trace, fewer segment nodes)")
_rule("conv_bn_relu", "fusion",
      "Convolution -> eval-mode BatchNorm (-> relu) folds into "
      "_fused_conv_bn_relu — the lazy-level generalization of the "
      "symbol-level TPU_FUSE pass (symbol/fusion.py shares "
      "fused_conv_bn_attrs)", levels=("lazy", "symbol"), parity="ulp")
_rule("map_reduce", "fusion",
      "a dead unary elementwise chain (>= 2 links) feeding sum/mean/max/"
      "min merges into one _rw_map_reduce node (same fns, same trace)")
_rule("spmd_constraint", "sharding",
      "inject _rw_sharding_constraint at large leaves per the spmd "
      "residency plan (shape-only infer_param_sharding); trivial meshes "
      "get replicated annotations that lower to zero collectives")


def rule_names():
    return tuple(RULES)


def fused_conv_bn_attrs(conv_attrs, bn_attrs, with_relu):
    """The `_fused_conv_bn_relu` attr dict from a Convolution + BatchNorm
    attr pair — the ONE place the conv+bn fold's parameters are assembled;
    both the lazy rule here and symbol/fusion.py's TPU_FUSE property call
    it, so the two levels can never drift."""
    attrs = {k: v for k, v in dict(conv_attrs).items()
             if k in ("kernel", "stride", "dilate", "pad", "num_filter",
                      "num_group", "layout")}
    bn = dict(bn_attrs)
    attrs["eps"] = bn.get("eps", 1e-3)
    attrs["fix_gamma"] = bn.get("fix_gamma", True)
    attrs["with_relu"] = bool(with_relu)
    return attrs


# ---------------------------------------------------------------------------
# gates (env knobs memoized on the raw string — the graph.py pattern)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=16)
def _parse_enabled(raw):
    return raw not in ("0", "false", "False")


def enabled():
    """MXNET_LAZY_REWRITE — default ON (active only inside a lazy flush,
    so MXNET_LAZY still gates everything)."""
    import os

    raw = os.environ.get("MXNET_LAZY_REWRITE")
    return raw is None or _parse_enabled(raw)


@functools.lru_cache(maxsize=32)
def _parse_disabled(raw):
    names = frozenset(s.strip() for s in (raw or "").split(",") if s.strip())
    unknown = names - frozenset(RULES)
    if unknown:
        # loud, once per distinct value: a typo here silently re-enables
        telemetry.counter("lazy.rewrite.unknown_disable_names").inc()
    return names


def disabled_rules():
    """MXNET_LAZY_REWRITE_DISABLE as a frozenset of rule names."""
    import os

    return _parse_disabled(os.environ.get("MXNET_LAZY_REWRITE_DISABLE"))


def config_token():
    """Hashable token of everything that can change the rewrite output
    for a fixed input signature: the disabled-rule set and (when the
    sharding rule is live) the spmd mesh + size floor. Part of the
    rewritten cache key — a mesh or gate flip compiles fresh."""
    dis = disabled_rules()
    spmd_token = None
    if "spmd_constraint" not in dis:
        import os

        if str(os.environ.get("MXNET_SPMD") or "").strip():
            try:
                from ..base import getenv
                from ..parallel import spmd as _spmd

                spmd_token = (_spmd.spmd_mesh(),
                              int(getenv("MXNET_SPMD_FSDP_MIN_SIZE")))
            except Exception:  # noqa: BLE001 — unsatisfiable spec: no rule
                spmd_token = None
    return (dis, spmd_token)


# ---------------------------------------------------------------------------
# IR — a tiny mutable view over the renumbered segment specs.
# refs: ("n",) | ("l", leaf_idx) | (_RNode, out_idx)
# ---------------------------------------------------------------------------

class _RNode:
    __slots__ = ("op_name", "frozen", "kind", "ins", "n_flat")

    def __init__(self, op_name, frozen, kind, ins, n_flat):
        self.op_name = op_name
        self.frozen = frozen      # hashable attr tuple (registry._freeze)
        self.kind = kind          # 'op' | 'vjp'
        self.ins = list(ins)
        self.n_flat = n_flat

    def attrs(self):
        return dict(self.frozen)


def _parse(specs, out_spec):
    nodes = []
    for op_name, frozen, kind, ins, n_flat in specs:
        rins = []
        for r in ins:
            if r == ("n",):
                rins.append(("n",))
            elif r[0] == "l":
                rins.append(("l", r[1]))
            else:  # ("s", (k, i))
                k, i = r[1]
                rins.append((nodes[k], i))
        nodes.append(_RNode(op_name, frozen, kind, rins, n_flat))
    outs = [(nodes[k], i) for (k, i) in out_spec]
    return nodes, outs


def _is_node_ref(r):
    return isinstance(r[0], _RNode)


def _apply_sub(nodes, outs, sub):
    """Rewrite every input/output ref through the substitution map
    (chains resolve transitively; subs only ever point backward in topo
    order, so no cycles)."""
    if not sub:
        return

    def res(r):
        while _is_node_ref(r):
            nxt = sub.get((r[0], r[1]))
            if nxt is None:
                return r
            r = nxt
        return r

    for n in nodes:
        n.ins = [r if not _is_node_ref(r) else res(r) for r in n.ins]
    outs[:] = [r if not _is_node_ref(r) else res(r) for r in outs]


def _uses(nodes, outs):
    """(use-count per slot, set of slots that are live outputs, consumer
    map slot -> [nodes])."""
    uses = collections.Counter()
    consumers = collections.defaultdict(list)
    for n in nodes:
        for r in n.ins:
            if _is_node_ref(r):
                uses[(r[0], r[1])] += 1
                consumers[(r[0], r[1])].append(n)
    out_slots = set()
    for r in outs:
        if _is_node_ref(r):
            uses[(r[0], r[1])] += 1
            out_slots.add((r[0], r[1]))
    return uses, out_slots, consumers


def _prune(nodes, outs):
    """Drop nodes no longer reachable from the live outputs — run after
    every pass so a substituted-away consumer stops inflating the use
    counts the fusion patterns key on."""
    reach = set()
    stack = [r[0] for r in outs if _is_node_ref(r)]
    while stack:
        n = stack.pop()
        if n in reach:
            continue
        reach.add(n)
        for r in n.ins:
            if _is_node_ref(r):
                stack.append(r[0])
    nodes[:] = [n for n in nodes if n in reach]


def _compute_avals(nodes, leaf_avals):
    """(shape, dtype) per (node, flat-out-idx), from the SAME cached
    abstract eval the recorder used — every key is a cache hit, so this
    pass is near-free on the plan-computation (cold) path. A node that
    cannot be abstractly evaluated simply has no entry (shape-checked
    rules skip it)."""
    from .graph import _abstract_eval

    avals = {}
    for n in nodes:
        in_sig = []
        ok = True
        for r in n.ins:
            if r == ("n",):
                in_sig.append(None)
            elif r[0] == "l":
                in_sig.append(leaf_avals[r[1]])
            else:
                a = avals.get((r[0], r[1]))
                if a is None:
                    ok = False
                    break
                in_sig.append(a)
        if not ok:
            continue
        try:
            ae = _abstract_eval(n.op_name, n.frozen, tuple(in_sig),
                                n.kind == "vjp")
        except Exception:  # noqa: BLE001 — no aval, shape rules skip
            ae = None
        if ae is None:
            continue
        out_avals, _single, _td, p_avals = ae
        flat = tuple(out_avals) + tuple(p_avals)
        if len(flat) != n.n_flat:
            continue
        for i, a in enumerate(flat):
            avals[(n, i)] = a
    return avals


# ---------------------------------------------------------------------------
# rule implementations — each returns the number of applications and
# mutates (nodes, outs) + a substitution map applied by the driver
# ---------------------------------------------------------------------------

_ADD_OPS = frozenset({"elemwise_add", "broadcast_add"})
_SUB_OPS = frozenset({"elemwise_sub", "broadcast_sub"})
_MUL_OPS = frozenset({"elemwise_mul", "broadcast_mul"})
_ZERO_OPS = frozenset({"_zeros", "zeros_like"})
_ONE_OPS = frozenset({"_ones", "ones_like"})

# unary links safe for the map_reduce chain merge: pure elementwise,
# attr-free, single-output (the fused node re-invokes the same fns)
_MR_UNARY = frozenset({
    "exp", "log", "log1p", "expm1", "sqrt", "rsqrt", "square", "abs",
    "tanh", "sigmoid", "relu", "negative", "erf", "sin", "cos",
})
_MR_REDUCE = frozenset({"sum", "mean", "max", "min"})


def _is_relu_like(n):
    if n.kind != "op":
        return False
    if n.op_name == "relu":
        return True
    return n.op_name == "Activation" and \
        str(n.attrs().get("act_type", "relu")) == "relu"


def _producer(r):
    """The producing op-kind node of a ref, or None."""
    if _is_node_ref(r) and r[0].kind == "op":
        return r[0]
    return None


def _pass_identity(nodes, outs, leaf_avals, avals):
    count = 0
    sub = {}

    def res(r):
        while _is_node_ref(r):
            nxt = sub.get((r[0], r[1]))
            if nxt is None:
                return r
            r = nxt
        return r

    def aval(r):
        if r == ("n",):
            return None
        if r[0] == "l":
            return leaf_avals[r[1]]
        return avals.get((r[0], r[1]))

    def norm_axes(n, ndim):
        ax = n.attrs().get("axes")
        if ax in (None, (), ""):
            return tuple(reversed(range(ndim)))
        return tuple(int(a) % ndim for a in ax)

    changed = True
    while changed:
        changed = False
        for n in nodes:
            if n.kind != "op" or n.n_flat != 1 or (n, 0) in sub:
                continue
            out_a = avals.get((n, 0))
            rep = None
            ins = [res(r) for r in n.ins]
            if n.op_name in _ADD_OPS and len(ins) == 2:
                a, b = ins
                pa, pb = _producer(a), _producer(b)
                if pb is not None and pb.op_name in _ZERO_OPS \
                        and out_a is not None and out_a == aval(a):
                    rep = a
                elif pa is not None and pa.op_name in _ZERO_OPS \
                        and out_a is not None and out_a == aval(b):
                    rep = b
            elif n.op_name in _SUB_OPS and len(ins) == 2:
                a, b = ins
                pb = _producer(b)
                if pb is not None and pb.op_name in _ZERO_OPS \
                        and out_a is not None and out_a == aval(a):
                    rep = a
            elif n.op_name in _MUL_OPS and len(ins) == 2:
                a, b = ins
                pa, pb = _producer(a), _producer(b)
                if pb is not None and pb.op_name in _ONE_OPS \
                        and out_a is not None and out_a == aval(a):
                    rep = a
                elif pa is not None and pa.op_name in _ONE_OPS \
                        and out_a is not None and out_a == aval(b):
                    rep = b
            elif n.op_name in ("_plus_scalar", "_minus_scalar") and ins:
                if float(n.attrs().get("scalar", 0.0)) == 0.0:
                    rep = ins[0]
            elif n.op_name in ("_mul_scalar", "_div_scalar") and ins:
                if float(n.attrs().get("scalar", 0.0)) == 1.0:
                    rep = ins[0]
            elif n.op_name == "negative" and ins:
                p = _producer(ins[0])
                if p is not None and p.op_name == "negative" \
                        and ins[0][1] == 0:
                    rep = res(p.ins[0])
            elif n.op_name == "transpose" and ins:
                p = _producer(ins[0])
                a = out_a
                if p is not None and p.op_name == "transpose" \
                        and ins[0][1] == 0 and a is not None:
                    ndim = len(a[0])
                    p1 = norm_axes(p, ndim)
                    p2 = norm_axes(n, ndim)
                    if tuple(p1[p2[i]] for i in range(ndim)) \
                            == tuple(range(ndim)):
                        rep = res(p.ins[0])
            elif n.op_name == "identity" and ins:
                rep = ins[0]
            if rep is not None:
                sub[(n, 0)] = rep
                count += 1
                changed = True
    _apply_sub(nodes, outs, sub)
    return count


def _pass_cse(nodes, outs):
    count = 0
    sub = {}
    idx = {n: i for i, n in enumerate(nodes)}
    seen = {}

    def res(r):
        while _is_node_ref(r):
            nxt = sub.get((r[0], r[1]))
            if nxt is None:
                return r
            r = nxt
        return r

    for n in nodes:
        if n.kind != "op":
            continue
        key_ins = []
        for r in n.ins:
            r = res(r) if _is_node_ref(r) else r
            if _is_node_ref(r):
                key_ins.append(("s", idx[r[0]], r[1]))
            else:
                key_ins.append(r)
        key = (n.op_name, n.frozen, tuple(key_ins), n.n_flat)
        rep = seen.get(key)
        if rep is None:
            seen[key] = n
        else:
            for i in range(n.n_flat):
                sub[(n, i)] = (rep, i)
            count += 1
    _apply_sub(nodes, outs, sub)
    return count


def _pass_dense_bias_act(nodes, outs):
    from ..ops.registry import _freeze

    uses, out_slots, _cons = _uses(nodes, outs)
    sub = {}
    count = 0
    rebuilt = []
    for n in nodes:
        if _is_relu_like(n) and n.n_flat == 1 and n.ins:
            r_add = n.ins[0]
            add = _producer(r_add)
            if add is not None and r_add[1] == 0 \
                    and add.op_name in _ADD_OPS and add.n_flat == 1 \
                    and uses[(add, 0)] == 1 and (add, 0) not in out_slots \
                    and len(add.ins) == 2:
                dot_ref = bias_ref = None
                for cand, other in ((add.ins[0], add.ins[1]),
                                    (add.ins[1], add.ins[0])):
                    d = _producer(cand)
                    if d is not None and cand[1] == 0 \
                            and d.op_name == "dot" and d.n_flat == 1 \
                            and uses[(d, 0)] == 1 \
                            and (d, 0) not in out_slots \
                            and len(d.ins) == 2:
                        dot_ref, bias_ref = cand, other
                        break
                if dot_ref is not None:
                    d = dot_ref[0]
                    dat = d.attrs()
                    fused = _RNode(
                        "_rw_dense_bias_act",
                        _freeze({"transpose_a": dat.get("transpose_a", False),
                                 "transpose_b": dat.get("transpose_b", False),
                                 "act": "relu"}),
                        "op", [d.ins[0], d.ins[1], bias_ref], 1)
                    rebuilt.append(fused)
                    sub[(n, 0)] = (fused, 0)
                    count += 1
        rebuilt.append(n)
    nodes[:] = rebuilt
    _apply_sub(nodes, outs, sub)
    return count


def _pass_conv_bn_relu(nodes, outs):
    from ..ops._utils import parse_bool
    from ..ops.registry import _freeze

    uses, out_slots, consumers = _uses(nodes, outs)
    sub = {}
    count = 0
    inserts = {}  # target node -> [new nodes to place before it]
    fused_for = {}  # BN node -> (fused node, relu node or None)
    for b in nodes:
        if b.kind != "op" or b.op_name != "BatchNorm" or b.n_flat != 3 \
                or len(b.ins) != 5:
            continue
        battrs = b.attrs()
        if parse_bool(battrs.get("_train", False)):
            continue  # train-mode BN updates stats: fold is eval-only
        if int(battrs.get("axis", 1)) != 1:
            continue  # the fold scales weight dim 0 (NCHW channel axis)
        conv_ref = b.ins[0]
        c = _producer(conv_ref)
        if c is None or conv_ref[1] != 0 or c.op_name != "Convolution" \
                or uses[(c, 0)] != 1 or (c, 0) in out_slots:
            continue
        cattrs = c.attrs()
        if str(cattrs.get("layout", "NCHW")) != "NCHW":
            continue
        data, weight = c.ins[0], c.ins[1]
        new_nodes = []
        if len(c.ins) >= 3 and not parse_bool(cattrs.get("no_bias", False)):
            bias = c.ins[2]
        else:
            nf = int(cattrs.get("num_filter", 0))
            if nf <= 0:
                continue
            zero = _RNode("_zeros",
                          _freeze({"shape": (nf,), "dtype": "float32"}),
                          "op", [], 1)
            new_nodes.append(zero)
            bias = (zero, 0)
        # optional trailing relu: single consumer of the BN main output
        relu = None
        if uses[(b, 0)] == 1 and (b, 0) not in out_slots:
            cand = consumers[(b, 0)][0]
            if _is_relu_like(cand) and cand.n_flat == 1 \
                    and cand.ins and cand.ins[0] == (b, 0):
                relu = cand
        attrs = fused_conv_bn_attrs(cattrs, battrs, relu is not None)
        fused = _RNode("_fused_conv_bn_relu", _freeze(attrs), "op",
                       [data, weight, bias, b.ins[1], b.ins[2],
                        b.ins[3], b.ins[4]], 1)
        new_nodes.append(fused)
        target = relu if relu is not None else b
        inserts.setdefault(target, []).extend(new_nodes)
        fused_for[b] = (fused, relu)
        count += 1
    if count:
        rebuilt = []
        for n in nodes:
            rebuilt.extend(inserts.get(n, ()))
            rebuilt.append(n)
        nodes[:] = rebuilt
        for b, (fused, relu) in fused_for.items():
            if relu is not None:
                sub[(relu, 0)] = (fused, 0)
            else:
                sub[(b, 0)] = (fused, 0)
            # eval-mode BN passes the moving stats through untouched:
            # outputs 1/2 ARE inputs 3/4 (bit-exact), so live aux slots
            # and the frontend's mutate_aux writeback keep their values
            sub[(b, 1)] = b.ins[3]
            sub[(b, 2)] = b.ins[4]
        _apply_sub(nodes, outs, sub)
    return count


def _pass_map_reduce(nodes, outs):
    from ..ops.registry import _freeze

    uses, out_slots, _cons = _uses(nodes, outs)
    sub = {}
    count = 0
    rebuilt = []
    for n in nodes:
        if n.kind == "op" and n.op_name in _MR_REDUCE and n.n_flat == 1 \
                and len(n.ins) == 1 and (n, 0) not in sub:
            steps = []
            cur = n.ins[0]
            while True:
                p = _producer(cur)
                if p is None or cur[1] != 0 or p.n_flat != 1 \
                        or p.op_name not in _MR_UNARY or p.frozen != () \
                        or len(p.ins) != 1 or uses[(p, 0)] != 1 \
                        or (p, 0) in out_slots:
                    break
                steps.append(p.op_name)
                cur = p.ins[0]
            if len(steps) >= 2:
                fused = _RNode(
                    "_rw_map_reduce",
                    _freeze({"steps": ",".join(reversed(steps)),
                             "reduce_op": n.op_name,
                             "reduce_attrs": n.frozen}),
                    "op", [cur], 1)
                rebuilt.append(fused)
                sub[(n, 0)] = (fused, 0)
                count += 1
        rebuilt.append(n)
    nodes[:] = rebuilt
    _apply_sub(nodes, outs, sub)
    return count


def _pass_spmd_constraint(nodes, outs, leaf_avals, spmd_token):
    from ..ops.registry import _freeze
    from ..parallel.spmd import infer_param_sharding

    mesh, min_size = spmd_token
    used_leaves = set()
    for n in nodes:
        for r in n.ins:
            if not _is_node_ref(r) and r != ("n",) and r[0] == "l":
                used_leaves.add(r[1])
    cands = {}
    for j in sorted(used_leaves):
        shape = leaf_avals[j][0]
        size = 1
        for s in shape:
            size *= int(s)
        if size >= int(min_size) and shape:
            cands[j] = shape
    if not cands:
        return 0
    trivial = int(mesh.devices.size) == 1
    plan = infer_param_sharding(mesh, None, cands,
                                residency_axes=tuple(mesh.axis_names))
    count = 0
    front = []
    wires = {}  # leaf idx -> constraint node
    for j in sorted(cands):
        spec = tuple(plan.get(j, ()))
        if all(p is None for p in spec):
            if not trivial:
                continue  # replicated on a real mesh: annotation buys nothing
            spec = ()  # trivial mesh: a pure layout annotation (the tp=1
            #            zero-collectives pin in test_lazy_rewrite)
        node = _RNode("_rw_sharding_constraint",
                      _freeze({"mesh": mesh, "spec": spec}),
                      "op", [("l", j)], 1)
        front.append(node)
        wires[j] = node
        count += 1
    if count:
        injected = set(front)
        for n in nodes:
            if n in injected:
                continue
            n.ins = [(wires[r[1]], 0)
                     if (not _is_node_ref(r) and r != ("n",) and r[0] == "l"
                         and r[1] in wires) else r
                     for r in n.ins]
        nodes[:] = front + nodes
    return count


# ---------------------------------------------------------------------------
# linearize back into replay specs
# ---------------------------------------------------------------------------

def _linearize(nodes, outs, leaf_avals):
    reach = set()
    stack = [r[0] for r in outs if _is_node_ref(r)]
    while stack:
        n = stack.pop()
        if n in reach:
            continue
        reach.add(n)
        for r in n.ins:
            if _is_node_ref(r):
                stack.append(r[0])
    kept = [n for n in nodes if n in reach]

    leaf_sel, leaf_map = [], {}

    def lref(j):
        if j not in leaf_map:
            leaf_map[j] = len(leaf_sel)
            leaf_sel.append(j)
        return leaf_map[j]

    idx = {}
    specs = []
    for k, n in enumerate(kept):
        ins = []
        for r in n.ins:
            if r == ("n",):
                ins.append(("n",))
            elif not _is_node_ref(r):
                ins.append(("l", lref(r[1])))
            else:
                ins.append(("s", (idx[r[0]], r[1])))
        idx[n] = k
        specs.append((n.op_name, n.frozen, n.kind, tuple(ins), n.n_flat))
    out_spec = []
    for r in outs:
        if _is_node_ref(r):
            out_spec.append((idx[r[0]], r[1]))
        else:
            out_spec.append(("l", lref(r[1])))
    leaf_avals2 = tuple(leaf_avals[j] for j in leaf_sel)
    return tuple(specs), tuple(out_spec), tuple(leaf_sel), leaf_avals2


# ---------------------------------------------------------------------------
# plan memo — steady-state flushes pay one OrderedDict hit
# ---------------------------------------------------------------------------

class Plan:
    __slots__ = ("specs", "out_spec", "leaf_sel", "leaf_avals", "stats",
                 "cfg")

    def __init__(self, specs, out_spec, leaf_sel, leaf_avals, stats, cfg):
        self.specs = specs
        self.out_spec = out_spec
        self.leaf_sel = leaf_sel
        self.leaf_avals = leaf_avals
        self.stats = stats    # {"rules": ((name, n), ...), "nodes_pre": .,
        #                        "nodes_post": .}
        self.cfg = cfg

    def cache_key(self):
        """The POST-rewrite CompileCache('lazy') key: namespaced so a
        rewritten program can never collide with an unrewritten one, and
        carrying the config token so gate/mesh flips compile fresh."""
        return ("rw", self.cfg, self.specs, self.leaf_avals, self.out_spec)


_PLANS = collections.OrderedDict()
_PLANS_LOCK = analysis.make_lock("lazy.rewrite_plans")
_PLANS_BOUND = 512
_MISS = object()


def plan_for(sig):
    """Memoized rewrite plan for a renumbered segment signature, or None
    when no rule fires (the caller then uses the ORIGINAL signature and
    cache entry — rewrite-on and rewrite-off share executables for
    segments the rewriter leaves alone)."""
    cfg = config_token()
    key = (cfg, sig)
    with _PLANS_LOCK:
        hit = _PLANS.get(key, _MISS)
        if hit is not _MISS:
            _PLANS.move_to_end(key)
            return hit
    try:
        plan = _compute_plan(sig, cfg)
    except Exception:  # noqa: BLE001 — a planner bug must degrade to
        #               the unrewritten (always-correct) program
        telemetry.counter("lazy.rewrite.plan_errors").inc()
        plan = None
    with _PLANS_LOCK:
        _PLANS[key] = plan
        while len(_PLANS) > _PLANS_BOUND:
            _PLANS.popitem(last=False)
    return plan


def _compute_plan(sig, cfg):
    specs, leaf_avals, out_spec = sig
    dis, spmd_token = cfg
    if not specs:
        return None
    live = [r for r in rule_names() if r not in dis
            and (r != "spmd_constraint" or spmd_token is not None)]
    if not live:
        return None
    nodes, outs = _parse(specs, out_spec)
    avals = _compute_avals(nodes, leaf_avals)
    applied = []

    def run(name, fn, *args):
        if name in live:
            n = fn(*args)
            if n:
                applied.append((name, n))
                _prune(nodes, outs)

    run("identity", _pass_identity, nodes, outs, leaf_avals, avals)
    run("cse", _pass_cse, nodes, outs)
    run("dense_bias_act", _pass_dense_bias_act, nodes, outs)
    run("conv_bn_relu", _pass_conv_bn_relu, nodes, outs)
    run("map_reduce", _pass_map_reduce, nodes, outs)
    if spmd_token is not None:
        run("spmd_constraint", _pass_spmd_constraint, nodes, outs,
            leaf_avals, spmd_token)
    if not applied:
        return None
    specs2, out_spec2, leaf_sel, leaf_avals2 = \
        _linearize(nodes, outs, leaf_avals)
    stats = {"rules": tuple(applied), "nodes_pre": len(specs),
             "nodes_post": len(specs2)}
    return Plan(specs2, out_spec2, leaf_sel, leaf_avals2, stats, cfg)


def note_applied(plan):
    """Per-flush telemetry for a rewritten segment (counted every flush,
    not once per plan, so steady-state traffic shows up in rates;
    tools/telemetry_report.py renders the 'rewrite:' line and
    telemetry.snapshot() derives lazy.rewrite.shrink_ratio and the
    pre/post mean ops per rewritten segment)."""
    telemetry.counter("lazy.rewrite.segments").inc()
    for name, n in plan.stats["rules"]:
        telemetry.counter(f"lazy.rewrite.rules_applied.{name}").inc(n)
    pre = plan.stats["nodes_pre"]
    post = plan.stats["nodes_post"]
    telemetry.counter("lazy.rewrite.nodes_pre").inc(pre)
    telemetry.counter("lazy.rewrite.nodes_post").inc(post)
    if pre > post:
        telemetry.counter("lazy.rewrite.nodes_eliminated").inc(pre - post)
