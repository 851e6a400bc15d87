"""The tpulint checkers — one function per framework invariant.

Each checker is pure AST analysis (lexical, no imports of the checked
code) and returns :class:`~tools.tpulint.Finding`\\ s. Lexical means
conservative: a rule only fires on patterns it can PROVE from the text
of one module, so every firing is actionable; transitive flows (a jitted
function calling a helper that reads the clock) are out of scope by
design — the runtime half (:mod:`mxnet_tpu.analysis`) covers dynamic
behavior.
"""
from __future__ import annotations

import ast
import re

from . import Finding, RULES

# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

# callables that produce (or wrap into) compiled executables
_JIT_NAMES = {"jit", "pjit", "pmap", "shard_map", "custom_vjp"}


def _call_name(node):
    """The rightmost name of a Call's func: jax.jit -> 'jit'."""
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _is_jit_call(node):
    return isinstance(node, ast.Call) and _call_name(node) in _JIT_NAMES


def _contains_jit_call(node):
    """Any reference to a jit-family builder in the subtree — a call
    (``jax.jit(f)``), a decorator (``@jax.custom_vjp``), or a bare
    reference passed along (``partial(jit, ...)``)."""
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id in _JIT_NAMES:
            return True
        if isinstance(n, ast.Attribute) and n.attr in _JIT_NAMES:
            return True
    return False


def _has_donate_kw(node):
    """Any call in the subtree passing donate_argnums/donate_argnames."""
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            for kw in n.keywords:
                if kw.arg in ("donate_argnums", "donate_argnames"):
                    return True
    return False


def _def_lines(node):
    """Lines whose disable comment covers a function-level finding: the
    def line plus every decorator line."""
    lines = [node.lineno]
    lines.extend(d.lineno for d in getattr(node, "decorator_list", ()))
    return tuple(lines)


def _kw(call, name):
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _is_false(node):
    return isinstance(node, ast.Constant) and node.value is False


# ---------------------------------------------------------------------------
# executable-cache: compiled executables live in a named CompileCache
# ---------------------------------------------------------------------------


def _functools_memo_aliases(tree):
    """Local names bound to functools.cache / functools.lru_cache via
    ``from functools import cache [as c]`` — `@cache` is the most natural
    3.9+ memo spelling and must not evade the rule."""
    names = set()
    for node in tree.body:
        if isinstance(node, ast.ImportFrom) and node.module == "functools":
            for alias in node.names:
                if alias.name in ("cache", "lru_cache"):
                    names.add(alias.asname or alias.name)
    return names


def _is_memo_decorator(dec, memo_aliases=frozenset()):
    """functools.lru_cache / lru_cache / functools.cache — bare, imported
    under any alias, or called (@lru_cache(maxsize=None))."""
    if isinstance(dec, ast.Call):
        dec = dec.func
    if isinstance(dec, ast.Name):
        return dec.id == "lru_cache" or dec.id in memo_aliases
    if isinstance(dec, ast.Attribute):
        if dec.attr == "lru_cache":
            return True
        return (dec.attr == "cache" and isinstance(dec.value, ast.Name)
                and dec.value.id == "functools")
    return False


def check_executable_cache(sf):
    """No ``lru_cache``/dict memo whose value flows from ``jax.jit`` /
    ``shard_map`` / ``pmap`` / ``custom_vjp``: anonymous memos recompile
    silently on shape churn and are invisible to ``named_stats`` — the
    exact failure BENCH_r05 could not attribute. Use a named
    ``CompileCache`` (the repo-wide rule since PR 3)."""
    out = []
    memo_aliases = _functools_memo_aliases(sf.tree)
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if (any(_is_memo_decorator(d, memo_aliases)
                    for d in node.decorator_list)
                    and _contains_jit_call(node)):
                out.append(Finding(
                    sf.path, node.lineno, "executable-cache",
                    f"'{node.name}' memoizes a compiled executable with "
                    f"lru_cache — use a named CompileCache so misses are "
                    f"attributable (compile_cache.named_stats)",
                    alt_lines=_def_lines(node)))
        elif isinstance(node, ast.Assign):
            if (any(isinstance(t, ast.Subscript) for t in node.targets)
                    and _contains_jit_call(node.value)):
                out.append(Finding(
                    sf.path, node.lineno, "executable-cache",
                    "dict-memoized compiled executable — use a named "
                    "CompileCache"))
        elif (isinstance(node, ast.Call)
              and _call_name(node) == "setdefault" and len(node.args) >= 2
              and _contains_jit_call(node.args[1])):
            out.append(Finding(
                sf.path, node.lineno, "executable-cache",
                "dict.setdefault-memoized compiled executable — use a "
                "named CompileCache"))
    return out


# ---------------------------------------------------------------------------
# cache-memory-tracking: big bounded caches pass track_memory=False
# ---------------------------------------------------------------------------

# bounded caches at or above this many entries are "many tiny programs":
# the /memory scrape's per-entry AOT analysis would re-pay a compile per
# entry for no insight (the op-cache / lazy-cache precedent)
_TRACK_MEMORY_BOUND = 128


def _donating_defs(tree):
    """scope-aware map: function node -> {name: has_donate} for its
    DIRECTLY nested defs (plus the module level), so `build` resolves to
    the builder in the same scope, not a same-named one elsewhere."""
    scopes = {}

    def scan(owner, body):
        local = {}
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                local[stmt.name] = _has_donate_kw(stmt)
        scopes[owner] = local

    scan(tree, tree.body)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scan(node, node.body)
    return scopes


def check_cache_memory_tracking(sf):
    """Bounded caches sized >= {bound} must pass ``track_memory=False`` —
    hundreds of tiny entries would each re-pay an AOT compile on the first
    /memory scrape."""
    out = []
    for node in ast.walk(sf.tree):
        if not (isinstance(node, ast.Call)
                and _call_name(node) == "CompileCache"):
            continue
        maxsize = _kw(node, "maxsize")
        if maxsize is None or (isinstance(maxsize, ast.Constant)
                               and maxsize.value is None):
            continue
        small = (isinstance(maxsize, ast.Constant)
                 and isinstance(maxsize.value, int)
                 and maxsize.value < _TRACK_MEMORY_BOUND)
        if not small and not _is_false(_kw(node, "track_memory")):
            out.append(Finding(
                sf.path, node.lineno, "cache-memory-tracking",
                f"bounded CompileCache sized >= {_TRACK_MEMORY_BOUND} "
                f"(or env-sized) must pass track_memory=False — the "
                f"/memory scrape AOT-recompiles every tracked entry"))
    return out


# ---------------------------------------------------------------------------
# donation-aliasing: every donate site resolves to an hlolint contract row
# ---------------------------------------------------------------------------
#
# The hlolint donation AUDIT (tools/hlolint) proves declared donations
# actually alias in the compiled program — but it can only audit programs
# whose cache entries carry a contract row. This rule closes the loop
# statically: a `donate_argnums`/`donate_argnames` executable built
# outside a named-CompileCache builder is invisible to the audit, and a
# builder whose row cannot be found in tools/hlolint/contracts.py is a
# contract hole.


def _hlolint_contract_rows():
    """The checked-in registry's tag set (None when unimportable — the
    structural checks still run; row validation is skipped rather than
    spraying false findings from an unrelated import error)."""
    try:
        from tools.hlolint.contracts import CONTRACTS

        return set(CONTRACTS)
    except Exception:  # noqa: BLE001 — registry validation is best-effort
        return None


def _compile_cache_literals(tree):
    """String names passed to CompileCache(...) in this module."""
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _call_name(node) == "CompileCache":
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                names.add(node.args[0].value)
    return names


def check_donation_aliasing(sf):
    """``donate_argnums``/``donate_argnames`` only inside a builder handed
    to ``CompileCache.get_or_build`` whose hlolint contract row exists:
    pass ``audit="<row>"`` (a literal found in
    ``tools/hlolint/contracts.py``), or let the cache name resolve to a
    row when the module constructs exactly one named ``CompileCache``. A
    donation the audit cannot see is exactly how "it silently stopped
    aliasing" regressions survive review."""
    out = []
    rows = _hlolint_contract_rows()
    cache_names = _compile_cache_literals(sf.tree)
    donating = _donating_defs(sf.tree)

    sanctioned_defs = set()     # builder def names referenced by any
    sanctioned_lambdas = set()  # get_or_build; id() for inline lambdas

    def builder_of(node):
        return node.args[1] if len(node.args) >= 2 else _kw(node, "build")

    gob_calls = []  # (call node, enclosing-def stack) — lexical builder
                    # resolution

    def collect(node, stack):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            stack = stack + [node]
        for child in ast.iter_child_nodes(node):
            collect(child, stack)
        if isinstance(node, ast.Call) \
                and _call_name(node) == "get_or_build":
            gob_calls.append((node, stack))

    collect(sf.tree, [])
    for node, stack in gob_calls:
        build = builder_of(node)
        is_donating = False
        if isinstance(build, ast.Lambda):
            sanctioned_lambdas.add(id(build))
            is_donating = _has_donate_kw(build)
        elif isinstance(build, ast.Name):
            sanctioned_defs.add(build.id)
            for scope in reversed([sf.tree] + stack):
                local = donating.get(scope, {})
                if build.id in local:
                    is_donating = local[build.id]
                    break
        if not is_donating:
            continue
        audit = _kw(node, "audit")
        if audit is None:
            if len(cache_names) == 1 and rows is not None \
                    and next(iter(cache_names)) not in rows:
                out.append(Finding(
                    sf.path, node.lineno, "donation-aliasing",
                    f"donating builder compiles under CompileCache"
                    f"({next(iter(cache_names))!r}) which has no contract "
                    f"row in tools/hlolint/contracts.py — add a row or an "
                    f"audit= tag so the donation audit can see it"))
            elif len(cache_names) != 1:
                out.append(Finding(
                    sf.path, node.lineno, "donation-aliasing",
                    "donating builder on a cache this module does not "
                    "construct — pass audit=\"<row>\" naming its "
                    "tools/hlolint/contracts.py contract row"))
        elif isinstance(audit, ast.Constant) \
                and isinstance(audit.value, str):
            if rows is not None and audit.value not in rows:
                out.append(Finding(
                    sf.path, node.lineno, "donation-aliasing",
                    f"audit={audit.value!r} names no contract row in "
                    f"tools/hlolint/contracts.py"))
        # a non-literal audit expression (the executor's composition
        # dispatch) is sanctioned — the runtime gate audits the real tag

    def walk(node, stack):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            stack = stack + [node]
        for child in ast.iter_child_nodes(node):
            walk(child, stack)
        if not isinstance(node, ast.Call):
            return
        if not any(kw.arg in ("donate_argnums", "donate_argnames")
                   for kw in node.keywords):
            return
        for scope in stack:
            if isinstance(scope, ast.Lambda):
                if id(scope) in sanctioned_lambdas:
                    return
            elif scope.name in sanctioned_defs:
                return
        out.append(Finding(
            sf.path, node.lineno, "donation-aliasing",
            "donated executable built outside a CompileCache.get_or_build "
            "builder — it is invisible to the hlolint donation audit "
            "(tools/hlolint); route it through a named cache"))

    walk(sf.tree, [])
    return out


# ---------------------------------------------------------------------------
# gate-discipline: no import-time side effects
# ---------------------------------------------------------------------------

_DEVICE_TOUCHES = {"devices", "local_devices", "device_count",
                   "local_device_count", "device_put", "default_backend"}


def _is_main_guard(node):
    """``if __name__ == "__main__":`` — script entry, exempt."""
    t = node.test
    return (isinstance(t, ast.Compare)
            and isinstance(t.left, ast.Name) and t.left.id == "__name__")


def _import_scope_statements(tree):
    """AST nodes executed at import: module-body statements, descending
    through If/Try/loops/With (headers included) but not into functions,
    classes, or the ``__main__`` guard. Compound statements yield their
    header expressions; their bodies are queued individually — each node
    is yielded exactly once."""
    work = list(tree.body)
    while work:
        stmt = work.pop(0)
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # the body runs later, but decorators and argument defaults
            # evaluate AT def time — i.e. at import for a module-level
            # (or class-level) def
            yield from stmt.decorator_list
            args = stmt.args
            for d in (*args.defaults, *args.kw_defaults):
                if d is not None:
                    yield d
            continue
        if isinstance(stmt, ast.ClassDef):
            # a class BODY executes at import: its statements, decorators
            # and base expressions are all import-scope
            yield from stmt.decorator_list
            yield from stmt.bases
            work.extend(stmt.body)
            continue
        if isinstance(stmt, ast.If) and _is_main_guard(stmt):
            continue
        if isinstance(stmt, ast.ExceptHandler):
            work.extend(stmt.body)
            continue
        compound = isinstance(stmt, (ast.If, ast.Try, ast.For, ast.While,
                                     ast.With))
        if not compound:
            yield stmt
            continue
        # headers run at import too (`if os.environ.get(...)`, `with X():`)
        for header in ("test", "iter"):
            h = getattr(stmt, header, None)
            if h is not None:
                yield h
        for item in getattr(stmt, "items", None) or ():
            # ast.withitem has no lineno — yield its expressions instead
            yield item.context_expr
            if item.optional_vars is not None:
                yield item.optional_vars
        for field in ("body", "orelse", "finalbody", "handlers"):
            work.extend(getattr(stmt, field, None) or ())


def _walk_pruning_defs(node):
    """``ast.walk`` that PRUNES nested function/class/lambda subtrees —
    their bodies execute later, not at import (line-range post-filtering
    would wrongly drop an import-scope finding that merely shares a line
    with a lambda)."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue
            stack.append(child)


def check_gate_discipline(sf):
    """Module import must be free of side effects: no thread starts, no
    raw ``os.environ``/``os.getenv`` parsing (the registered
    ``base.getenv`` helper is the sanctioned accessor), no device
    touches. Import-time work runs before any gate can be consulted and
    breaks the 'one attribute read when off' discipline (PR 7/11);
    import-time device touches wedge CPU-only processes (the PR 6 probe
    incident)."""
    out = []
    for stmt in _import_scope_statements(sf.tree):
        # one disable comment anywhere in a multi-line statement covers
        # every finding the statement produces
        span = tuple(range(stmt.lineno,
                           max(getattr(stmt, "end_lineno", stmt.lineno),
                               stmt.lineno) + 1))
        for node in _walk_pruning_defs(stmt):
            if isinstance(node, ast.Call):
                name = _call_name(node)
                fv = node.func
                if name == "start" and isinstance(fv, ast.Attribute):
                    out.append(Finding(
                        sf.path, node.lineno, "gate-discipline",
                        "thread/process started at import — start lazily "
                        "behind the subsystem's enable() gate",
                        alt_lines=span))
                elif name == "Thread":
                    out.append(Finding(
                        sf.path, node.lineno, "gate-discipline",
                        "Thread constructed at import — construct lazily "
                        "behind the subsystem's enable() gate",
                        alt_lines=span))
                elif (name == "getenv" and isinstance(fv, ast.Attribute)
                      and isinstance(fv.value, ast.Name)
                      and fv.value.id == "os"):
                    out.append(Finding(
                        sf.path, node.lineno, "gate-discipline",
                        "raw os.getenv at import — use the registered "
                        "base.getenv helper (typed defaults, documented "
                        "in docs/faq/env_var.md)",
                        alt_lines=span))
                elif (name in _DEVICE_TOUCHES
                      and isinstance(fv, ast.Attribute)
                      and isinstance(fv.value, ast.Name)
                      and fv.value.id == "jax"):
                    out.append(Finding(
                        sf.path, node.lineno, "gate-discipline",
                        f"device touch jax.{name}() at import — probe "
                        f"devices lazily (import must stay cheap and "
                        f"backend-agnostic)",
                        alt_lines=span))
            elif (isinstance(node, ast.Attribute)
                  and node.attr == "environ"
                  and isinstance(node.value, ast.Name)
                  and node.value.id == "os"):
                out.append(Finding(
                    sf.path, node.lineno, "gate-discipline",
                    "os.environ touched at import — parse env lazily "
                    "(or via base.getenv inside the gate helper)",
                    alt_lines=span))
    return out


# ---------------------------------------------------------------------------
# tracer-hygiene: no impure host reads inside traced functions
# ---------------------------------------------------------------------------

_CLOCK_ATTRS = {"time", "time_ns", "monotonic", "perf_counter",
                "perf_counter_ns", "monotonic_ns"}


def _traced_functions(tree):
    """Function defs handed to the tracer: jit-ish decorated, or named as
    the first argument of a jit-ish call anywhere in the module
    (including nested: jax.jit(shard_map(body, ...)))."""
    traced_names = set()

    def first_arg_names(call):
        if not call.args:
            return
        a = call.args[0]
        if isinstance(a, ast.Name):
            traced_names.add(a.id)
        elif isinstance(a, ast.Call):
            if _is_jit_call(a) or _call_name(a) in ("partial",):
                first_arg_names(a)

    for node in ast.walk(tree):
        if _is_jit_call(node):
            first_arg_names(node)
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        decorated = False
        for dec in node.decorator_list:
            d = dec.func if isinstance(dec, ast.Call) else dec
            if isinstance(d, (ast.Name, ast.Attribute)) \
                    and (d.id if isinstance(d, ast.Name) else d.attr) \
                    in _JIT_NAMES:
                decorated = True
            elif (isinstance(dec, ast.Call)
                  and _call_name(dec) == "partial" and dec.args
                  and isinstance(dec.args[0], (ast.Name, ast.Attribute))):
                a0 = dec.args[0]
                nm = a0.id if isinstance(a0, ast.Name) else a0.attr
                decorated = decorated or nm in _JIT_NAMES
        if decorated or node.name in traced_names:
            yield node


def check_tracer_hygiene(sf):
    """Functions traced by ``jax.jit``/``shard_map``/``pmap``/
    ``custom_vjp`` run ONCE at trace time: a ``time.time()``,
    ``datetime.now()``, ``np.random.*`` or env read inside them is
    baked into the compiled program as a constant — it looks dynamic,
    is not, and changes behavior between cache hit and miss. Read host
    state outside, pass it in as an argument (or jax PRNG keys for
    randomness)."""
    out = []
    for fn in _traced_functions(sf.tree):
        for node in ast.walk(fn):
            msg = None
            if isinstance(node, ast.Attribute):
                v = node.value
                if (node.attr in _CLOCK_ATTRS and isinstance(v, ast.Name)
                        and v.id == "time"):
                    msg = f"time.{node.attr} read"
                elif node.attr == "now" and isinstance(
                        v, (ast.Name, ast.Attribute)) and (
                        (isinstance(v, ast.Name)
                         and v.id == "datetime")
                        or (isinstance(v, ast.Attribute)
                            and v.attr == "datetime")):
                    msg = "datetime.now read"
                elif (isinstance(v, ast.Attribute) and v.attr == "random"
                        and isinstance(v.value, ast.Name)
                        and v.value.id in ("np", "numpy")):
                    msg = f"np.random.{node.attr} (host RNG)"
                elif (node.attr == "environ" and isinstance(v, ast.Name)
                        and v.id == "os"):
                    msg = "os.environ read"
            elif isinstance(node, ast.Call):
                nm = _call_name(node)
                if nm == "getenv":
                    msg = "env read (getenv)"
            if msg:
                out.append(Finding(
                    sf.path, node.lineno, "tracer-hygiene",
                    f"{msg} lexically inside traced function "
                    f"'{fn.name}' — traced once, then baked into the "
                    f"executable; hoist it out and pass the value in",
                    alt_lines=_def_lines(fn)))
    return out


# ---------------------------------------------------------------------------
# env-var-registry: code reads <-> docs/faq/env_var.md rows
# ---------------------------------------------------------------------------

# identifiers that match the MXNET_* shape but are not env knobs, plus
# knobs owned by processes outside the scanned tree (set for children,
# read by the test harness)
ENV_ALLOWLIST = {
    "MXNET_VERSION",              # package version constant, not an env var
}

_ENV_NAME_RE = re.compile(r"^MXNET_[A-Z0-9_]+$")
_ENV_DOC_ROW_RE = re.compile(r"^\|\s*`(MXNET_[A-Z0-9_]+)`")
_ENV_READ_CALLS = {"getenv", "register_env", "get", "setdefault", "pop"}


def _env_uses(sf):
    """(name, line, is_read) for every MXNET_* string constant in the
    module. is_read marks recognized env accessor sites (getenv /
    register_env / os.environ get-sibling calls / environ subscripts);
    any other occurrence still counts as a *use* for doc coverage."""
    uses = []
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Call):
            if _call_name(node) in _ENV_READ_CALLS and node.args:
                a = node.args[0]
                if isinstance(a, ast.Constant) and isinstance(a.value, str) \
                        and _ENV_NAME_RE.match(a.value):
                    uses.append((a.value, a.lineno, True))
        elif isinstance(node, ast.Subscript):
            s = node.slice
            if isinstance(s, ast.Constant) and isinstance(s.value, str) \
                    and _ENV_NAME_RE.match(s.value):
                uses.append((s.value, node.lineno, True))
        elif isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and _ENV_NAME_RE.match(node.value):
            uses.append((node.value, node.lineno, False))
    return uses


def check_env_registry(sources, env_doc):
    """Project-level rule: every ``MXNET_*`` knob READ in the scanned
    code has a row in ``docs/faq/env_var.md``, and every documented row
    is used somewhere in the code — both directions of the drift this PR
    found (MXNET_PALLAS_ATTENTION & co. were live but undocumented)."""
    try:
        doc_text = open(env_doc, encoding="utf-8").read()
    except OSError:
        return [Finding(env_doc, 1, "env-var-registry",
                        "env-var doc table not found")]
    doc_rows = {}
    for i, line in enumerate(doc_text.splitlines(), 1):
        m = _ENV_DOC_ROW_RE.match(line.strip())
        if m:
            doc_rows.setdefault(m.group(1), i)

    out, used = [], set()
    for sf in sources:
        for name, line, is_read in _env_uses(sf):
            used.add(name)
            if is_read and name not in doc_rows \
                    and name not in ENV_ALLOWLIST \
                    and not sf.disabled("env-var-registry", line):
                out.append(Finding(
                    sf.path, line, "env-var-registry",
                    f"{name} is read here but has no row in {env_doc} — "
                    f"document it (default + one-line semantics)"))
    for name, line in sorted(doc_rows.items()):
        if name not in used and name not in ENV_ALLOWLIST:
            out.append(Finding(
                env_doc, line, "env-var-registry",
                f"{name} is documented but never referenced in the "
                f"scanned code — stale row, or the knob lost its reader"))
    # dedupe repeated reads of the same undocumented name per file
    seen, deduped = set(), []
    for f in out:
        key = (f.path, f.rule, f.message.split(" ", 1)[0])
        if key in seen:
            continue
        seen.add(key)
        deduped.append(f)
    return deduped


RULES.update({
    "executable-cache": check_executable_cache,
    "cache-memory-tracking": check_cache_memory_tracking,
    "donation-aliasing": check_donation_aliasing,
    "gate-discipline": check_gate_discipline,
    "tracer-hygiene": check_tracer_hygiene,
    # env-var-registry is project-level (cross-file + doc table), so it
    # is NOT in this per-file map — lint_sources runs it directly; the
    # CLI adds its name for --list-rules and --select validation
})
