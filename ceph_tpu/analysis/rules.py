"""Rule set for the static analyzer.

Two families, both specific to this codebase's hazard classes:

JAX trace-safety (the `@jax.jit` kernels in ops/, ec/, models/):
  trace-side-effect    Python side effects baked in at trace time
  trace-host-sync      implicit device->host syncs inside traced code
  uint8-overflow       narrow-dtype arithmetic in the GF(2^8) paths
  trace-static-hazard  params needing static_argnums/static_argnames
  trace-numpy          bare numpy ops applied to traced values

async/daemon safety (the mon/osd/mds/rgw asyncio daemons):
  async-blocking       event-loop-blocking calls in `async def` bodies
  lock-order           static lock-order cycles (lockdep, at lint time)
  lock-no-await        un-awaited asyncio.Lock acquisition / sync `with`
  sync-encode-in-async direct ec_util.encode* / codec .encode() in
                       `async def` bodies under ceph_tpu/osd/ — the
                       encode runs ON the event loop instead of
                       riding the micro-batching encode service
                       (osd/encode_service.py)
  unhedged-gather      bare asyncio.gather over shard sub-op jobs in
                       ceph_tpu/osd/ outside the hedge primitive
                       (osd/hedge.py) — the fan-out completes at the
                       slowest peer's pace; all-shard write/absence
                       gathers are baselined with justifications
  span-leak            tracer.start(...) whose span is not finished
                       in a finally / context manager on every path —
                       a leaked span never reaches the ring, the
                       critical-path histograms, or the tail
                       exemplars; use `async with tracer.span(...)`
                       (common/tracing.py) or finish in a finally

EC dispatch discipline:
  jit-bypass-plan      direct jax.jit on shape-polymorphic EC entry
                       points that bypass the ExecPlan cache
                       (ceph_tpu/ec/plan.py): every shape retraces and
                       the compile is invisible to plan.stats()
  unguarded-device-dispatch
                       raw device dispatch (backend.matmul /
                       gf.gf_matmul_tpu / the pallas word kernels) in
                       ec/, ops/, osd/ outside the breaker guard
                       (common/circuit.py device_call): a wedged or
                       faulting accelerator surfaces as a raised
                       exception instead of degrading to the
                       bit-exact host path
  unplanned-mesh-dispatch
                       raw shard_map/pjit in ec/, osd/, parallel/
                       bypassing the plan cache (ec/plan.py
                       tracked_jit / mesh plan kinds) or the breaker
                       guard: the compile is invisible to
                       plan.stats(), binds a device set no health
                       shrink can retire, and dispatches without
                       watchdog or sick-chip attribution
  unplanned-compute-dispatch
                       raw coded-compute kernel invocation
                       (compute.kernels.device_eval) in compute/,
                       osd/ outside the plan cache (ec/plan.py
                       compute_eval) or circuit.device_call: the
                       compile is invisible to plan.stats() and the
                       dispatch has no watchdog or bit-exact host
                       degradation
  unscheduled-bitmatrix-xor
                       naive row-walk XOR loops (bitwise_xor.reduce /
                       subscripted ^= accumulation inside a loop) in
                       ec/ outside ec/xsched.py + ec/plan.py: the XOR
                       program bypasses the schedule compiler's CSE,
                       memoization and stats — execute a compiled
                       schedule (xsched.compile_matrix +
                       execute_host) instead; pure-GF multiply loops
                       (wide-word fields) are not XOR walks and are
                       exempt
  raw-process-group    jax.distributed.initialize/shutdown outside
                       the parallel/multihost.py bootstrap seam: a
                       process group joined elsewhere skips the gloo
                       CPU-collectives config, the host-topology
                       map, the plan keys' process-topology element,
                       and the collective-safe membership agreement
                       — host loss would wedge a collective instead
                       of reading as a timeout

store durability discipline:
  commit-before-durability
                       `on_commit`/ack callbacks in ceph_tpu/os/
                       reachable before the store's durability point
                       (block fsync / sync KV batch): the acked
                       transaction can vanish on power loss — the
                       invariant the crash sweep (os/faultstore.py)
                       checks dynamically, enforced here at lint time

inference serving discipline:
  unbudgeted-approx-result
                       an approximate combine (least-squares solve of
                       missing shard contributions feeding combined
                       scores) in ceph_tpu/inference/ returned without
                       consulting the error-budget gate
                       (inference/fisher.py check_budget): a result
                       whose estimated error nobody priced against the
                       caller's budget — every approximate serving
                       result must pass check_budget or yield to the
                       exact full-decode fallback

loadgen/bench discipline:
  unbounded-latency-buffer
                       appending per-op latency samples to a plain
                       list inside a loadgen/bench loop: an open-loop
                       sweep offers ops at a fixed rate regardless of
                       completions, so the buffer grows with offered
                       load times duration — stream into the bounded
                       log-bucket histogram
                       (ceph_tpu/loadgen/stats.py) instead

Every rule walks its own scope only (nested defs are analyzed as their
own traced/async functions), so findings never double-report.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, Optional, Set

from ceph_tpu.analysis.core import (
    Analyzer, _is_jit_expr, dotted, dynamic_names_in,
)

# numpy/stdlib call classification ------------------------------------

_BLOCKING_CALLS = {
    "time.sleep", "subprocess.run", "subprocess.call",
    "subprocess.check_call", "subprocess.check_output",
    "subprocess.getoutput", "subprocess.getstatusoutput",
    "os.system", "os.popen", "os.wait", "os.waitpid",
    "urllib.request.urlopen", "socket.create_connection",
}
_BLOCKING_PREFIXES = ("requests.",)
_NUMPY_ALIASES = {"np", "numpy"}
_NARROW_DTYPES = {"uint8", "int8"}
# numpy attrs that are fine on traced values (metadata / dtype ctors)
_NUMPY_SAFE_ATTRS = {
    "shape", "ndim", "dtype", "uint8", "int8", "uint16", "int16",
    "uint32", "int32", "uint64", "int64", "float16", "float32",
    "float64", "bool_", "newaxis", "pi", "e", "inf", "nan",
}
# host-sync builtins on a traced value.  len() is NOT here: on a
# traced array it reads the static leading dim (shape metadata), no
# sync and no trace error.
_SYNC_BUILTINS = {"float", "int", "bool", "complex"}


def walk_scope(root: ast.AST) -> Iterator[ast.AST]:
    """Walk a function body without descending into nested defs or
    classes (lambdas ARE included: they trace/run in this scope)."""
    stack = [c for c in ast.iter_child_nodes(root)]
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
            stack.extend(ast.iter_child_nodes(node))


def _resolved_callee(mod, node: ast.Call) -> str:
    """Dotted callee with the import table applied to the head, so
    `import subprocess as sp; sp.run` still reads 'subprocess.run'."""
    name = dotted(node.func)
    if not name:
        return ""
    head, _, rest = name.partition(".")
    src = mod.imports.get(head)
    if src is not None:
        src_mod, attr = src
        base = src_mod if attr is None else f"{src_mod}.{attr}"
        return f"{base}.{rest}" if rest else base
    return name


def _is_numpy_call(mod, node: ast.Call) -> Optional[str]:
    """Return the numpy attr name if this is a np.<attr>(...) call."""
    name = dotted(node.func)
    if not name:
        return None
    head, _, rest = name.partition(".")
    if not rest:
        return None
    src = mod.imports.get(head)
    base = head if src is None else src[0]
    if base in _NUMPY_ALIASES or base == "numpy":
        return rest.split(".")[0] if "." in rest else rest
    return None


def _args_tainted(node: ast.Call, tainted: Set[str]) -> bool:
    for arg in list(node.args) + [kw.value for kw in node.keywords]:
        if dynamic_names_in(arg) & tainted:
            return True
    return False


# ---------------------------------------------------------------------
# trace-side-effect
# ---------------------------------------------------------------------

def rule_trace_side_effect(a: Analyzer) -> None:
    for fi in a.project.traced_functions().values():
        mod = fi.module
        for node in walk_scope(fi.node):
            if isinstance(node, ast.Global):
                a.emit("trace-side-effect", mod, node,
                       f"`global {', '.join(node.names)}` inside traced "
                       f"`{fi.qualname}`: the mutation runs once at "
                       "trace time, not per call",
                       symbol=fi.qualname, scope_line=fi.lineno)
            elif isinstance(node, ast.Call):
                callee = _resolved_callee(mod, node)
                if callee == "print":
                    a.emit("trace-side-effect", mod, node,
                           f"print() inside traced `{fi.qualname}` fires "
                           "at trace time only (use jax.debug.print)",
                           symbol=fi.qualname, scope_line=fi.lineno)
                elif callee.startswith("time."):
                    a.emit("trace-side-effect", mod, node,
                           f"{callee}() inside traced `{fi.qualname}` is "
                           "evaluated once at trace time and baked into "
                           "the kernel",
                           symbol=fi.qualname, scope_line=fi.lineno)
                elif (callee.startswith(("numpy.random.", "random."))
                      or _is_numpy_call(mod, node) == "random"
                      or (_is_numpy_call(mod, node) or "").startswith(
                          "random")):
                    a.emit("trace-side-effect", mod, node,
                           f"host RNG inside traced `{fi.qualname}`: the "
                           "draw is frozen at trace time (thread "
                           "jax.random keys instead)",
                           symbol=fi.qualname, scope_line=fi.lineno)


# ---------------------------------------------------------------------
# trace-host-sync
# ---------------------------------------------------------------------

def rule_trace_host_sync(a: Analyzer) -> None:
    for fi in a.project.traced_functions().values():
        mod = fi.module
        tainted = a.project.tainted_locals(fi)
        for node in walk_scope(fi.node):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "item":
                if dynamic_names_in(f.value) & tainted:
                    a.emit("trace-host-sync", mod, node,
                           f".item() on a traced value in "
                           f"`{fi.qualname}` forces a device->host sync "
                           "(trace error under jit)",
                           symbol=fi.qualname, scope_line=fi.lineno)
            elif isinstance(f, ast.Name) and f.id in _SYNC_BUILTINS \
                    and node.args and _args_tainted(node, tainted):
                a.emit("trace-host-sync", mod, node,
                       f"{f.id}() on a traced value in `{fi.qualname}` "
                       "concretizes the tracer (host sync / trace "
                       "error)",
                       symbol=fi.qualname, scope_line=fi.lineno)
            else:
                np_attr = _is_numpy_call(mod, node)
                if np_attr in ("asarray", "array") and \
                        _args_tainted(node, tainted):
                    a.emit("trace-host-sync", mod, node,
                           f"np.{np_attr}() on a traced value in "
                           f"`{fi.qualname}` pulls the array to host "
                           "mid-trace (use jnp)",
                           symbol=fi.qualname, scope_line=fi.lineno)


# ---------------------------------------------------------------------
# uint8-overflow
# ---------------------------------------------------------------------

_OVERFLOW_OPS = {
    ast.Add: "+", ast.Sub: "-", ast.Mult: "*", ast.LShift: "<<",
    ast.Pow: "**",
}


def _scope_nodes(root: ast.AST) -> Iterator[ast.AST]:
    """Child nodes of one variable scope: descends classes but stops
    at nested function boundaries (each function in mod.functions gets
    its own scope pass)."""
    stack = list(ast.iter_child_nodes(root))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
            stack.extend(ast.iter_child_nodes(node))


def _dtype_is_narrow(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return node.value in _NARROW_DTYPES
    name = dotted(node)
    return bool(name) and name.split(".")[-1] in _NARROW_DTYPES


class _NarrowTracker(ast.NodeVisitor):
    """Heuristic per-module dtype tracker: an expression is 'narrow'
    (uint8/int8) if it is built by an explicit narrow construction —
    jnp.uint8(x), .astype(np.uint8), dtype=np.uint8 kwargs — or derives
    from a local known to be narrow."""

    def __init__(self) -> None:
        self.narrow_names: Set[str] = set()

    def is_narrow(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Call):
            name = dotted(node.func) or ""
            tail = name.split(".")[-1]
            if tail in _NARROW_DTYPES:
                return True
            if tail == "astype" and node.args and \
                    _dtype_is_narrow(node.args[0]):
                return True
            if tail == "view" and node.args and \
                    _dtype_is_narrow(node.args[0]):
                return True
            for kw in node.keywords:
                if kw.arg == "dtype" and _dtype_is_narrow(kw.value):
                    return True
            return False
        if isinstance(node, ast.Name):
            return node.id in self.narrow_names
        if isinstance(node, ast.Subscript):
            return self.is_narrow(node.value)
        if isinstance(node, ast.BinOp):
            return self.is_narrow(node.left) or \
                self.is_narrow(node.right)
        if isinstance(node, (ast.UnaryOp,)):
            return self.is_narrow(node.operand)
        return False

    def feed_assign(self, node: ast.AST) -> None:
        if isinstance(node, ast.Assign) and self.is_narrow(node.value):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    self.narrow_names.add(tgt.id)


def rule_uint8_overflow(a: Analyzer) -> None:
    patterns = a.config.get("dtype_paths", ("ops/gf", "ec/"))
    for mod in a.project.modules.values():
        rel = mod.relpath.replace("\\", "/")
        if patterns and not any(p in rel for p in patterns):
            continue
        # narrow-name tracking is scoped per function (plus module
        # scope) so a uint8 local in one function can't taint a
        # same-named name elsewhere
        module_tracker = _NarrowTracker()
        for node in _scope_nodes(mod.tree):
            module_tracker.feed_assign(node)

        def check_scope(root: ast.AST) -> None:
            tracker = _NarrowTracker()
            tracker.narrow_names = set(module_tracker.narrow_names)
            nodes = list(_scope_nodes(root))
            for node in nodes:  # learn locals first, then flag
                tracker.feed_assign(node)
            for node in nodes:
                if isinstance(node, ast.BinOp) and \
                        type(node.op) in _OVERFLOW_OPS and (
                            tracker.is_narrow(node.left)
                            or tracker.is_narrow(node.right)):
                    sym = _enclosing_qualname(mod, node)
                    a.emit(
                        "uint8-overflow", mod, node,
                        f"uint8/int8 `{_OVERFLOW_OPS[type(node.op)]}` "
                        "wraps silently at 256; promote an operand "
                        "(.astype(jnp.int32)) or justify in the "
                        "baseline", severity="warning",
                        symbol=sym, scope_line=_scope_line(mod, node))

        check_scope(mod.tree)
        for fi in mod.functions.values():
            check_scope(fi.node)


def _enclosing_qualname(mod, node: ast.AST) -> str:
    cur = node
    while cur is not None:
        cur = mod.parents.get(cur)
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for q, fi in mod.functions.items():
                if fi.node is cur:
                    return q
            return cur.name
    return "<module>"


def _scope_line(mod, node: ast.AST) -> int:
    cur = node
    while cur is not None:
        cur = mod.parents.get(cur)
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return cur.lineno
    return 0


# ---------------------------------------------------------------------
# trace-static-hazard
# ---------------------------------------------------------------------

def rule_trace_static_hazard(a: Analyzer) -> None:
    shape_ctors = {"zeros", "ones", "full", "empty", "arange",
                   "linspace", "eye", "broadcast_to"}
    for fi in a.project.traced_functions().values():
        if not fi.jit_decorated:
            continue
        mod = fi.module
        dynamic = set(fi.params) - fi.static_params - {"self"}
        names_in = dynamic_names_in

        for node in walk_scope(fi.node):
            hits: Set[str] = set()
            what = ""
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name) and \
                    node.func.id == "range" and node.args:
                hits = set().union(*(names_in(x) for x in node.args)) \
                    & dynamic
                what = "range() bound"
            elif isinstance(node, (ast.If, ast.While)):
                hits = names_in(node.test) & dynamic
                what = f"`{type(node).__name__.lower()}` condition"
            elif isinstance(node, ast.Call):
                tail = (dotted(node.func) or "").split(".")[-1]
                if tail in shape_ctors and node.args:
                    hits = names_in(node.args[0]) & dynamic
                    what = f"{tail}() shape"
            if hits:
                names = ", ".join(sorted(hits))
                a.emit("trace-static-hazard", mod, node,
                       f"param(s) {names} of jit'd `{fi.qualname}` "
                       f"drive a {what}: mark static_argnums/"
                       "static_argnames or every new value recompiles "
                       "(traced values here even error)",
                       severity="warning", symbol=fi.qualname,
                       scope_line=fi.lineno)


# ---------------------------------------------------------------------
# trace-numpy
# ---------------------------------------------------------------------

def rule_trace_numpy(a: Analyzer) -> None:
    for fi in a.project.traced_functions().values():
        mod = fi.module
        tainted = a.project.tainted_locals(fi)
        for node in walk_scope(fi.node):
            if not isinstance(node, ast.Call):
                continue
            np_attr = _is_numpy_call(mod, node)
            if np_attr is None or np_attr in _NUMPY_SAFE_ATTRS or \
                    np_attr in ("asarray", "array", "random"):
                continue  # asarray/array: rule trace-host-sync's beat
            if _args_tainted(node, tainted):
                a.emit("trace-numpy", mod, node,
                       f"np.{np_attr}() applied to a traced value in "
                       f"`{fi.qualname}`: numpy can't trace — use the "
                       "jnp equivalent", severity="warning",
                       symbol=fi.qualname, scope_line=fi.lineno)


# ---------------------------------------------------------------------
# async-blocking
# ---------------------------------------------------------------------

def rule_async_blocking(a: Analyzer) -> None:
    for mod in a.project.modules.values():
        for fi in mod.functions.values():
            if not fi.is_async:
                continue
            for node in walk_scope(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                callee = _resolved_callee(mod, node)
                blocking = (
                    callee in _BLOCKING_CALLS
                    or callee.startswith(_BLOCKING_PREFIXES))
                if callee == "open" and not _inside_lambda(mod, node):
                    a.emit("async-blocking", mod, node,
                           f"sync file I/O (open) in `async def "
                           f"{fi.qualname}` blocks the daemon's event "
                           "loop (asyncio.to_thread it)",
                           symbol=fi.qualname, scope_line=fi.lineno)
                elif blocking and not _inside_lambda(mod, node):
                    a.emit("async-blocking", mod, node,
                           f"{callee}() in `async def {fi.qualname}` "
                           "blocks the event loop for every task on "
                           "this daemon (await an async equivalent or "
                           "asyncio.to_thread)",
                           symbol=fi.qualname, scope_line=fi.lineno)


def _inside_lambda(mod, node: ast.AST) -> bool:
    """Calls inside a lambda run later (often shipped to an executor);
    the lambda boundary gets the benefit of the doubt."""
    cur = node
    while cur is not None:
        cur = mod.parents.get(cur)
        if isinstance(cur, ast.Lambda):
            return True
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return False
    return False


# ---------------------------------------------------------------------
# jit-bypass-plan
# ---------------------------------------------------------------------

# EC dispatch modules where jit compiles must route through the
# ExecPlan cache (ceph_tpu/ec/plan.py `tracked_jit` / a plan kind);
# the plan module itself is the one legitimate jit site.
_PLAN_PATHS = ("ec/", "ops/gf.py", "parallel/striped.py")
_PLAN_EXEMPT = ("ec/plan.py",)


def rule_jit_bypass_plan(a: Analyzer) -> None:
    """Direct jax.jit/pjit in the EC dispatch layers: every new shape
    pays a silent retrace outside the plan cache's bucketing, counters
    and LRU.  Route through ceph_tpu.ec.plan (tracked_jit or a plan
    kind), or baseline with a justification."""
    paths = a.config.get("plan_paths", _PLAN_PATHS)
    exempt = a.config.get("plan_exempt", _PLAN_EXEMPT)
    for mod in a.project.modules.values():
        rel = mod.relpath.replace("\\", "/")
        if not any(p in rel for p in paths):
            continue
        if any(e in rel for e in exempt):
            continue
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and _is_jit_expr(node.func):
                a.emit("jit-bypass-plan", mod, node,
                       "direct jax.jit in the EC dispatch layer "
                       "bypasses the ExecPlan cache: every new shape "
                       "retraces unseen by plan.stats() — use "
                       "ceph_tpu.ec.plan.tracked_jit or a plan kind",
                       severity="warning",
                       symbol=_enclosing_qualname(mod, node),
                       scope_line=_scope_line(mod, node))
        for fi in mod.functions.values():
            for dec in fi.node.decorator_list:
                direct = _is_jit_expr(dec)
                via_partial = (
                    isinstance(dec, ast.Call)
                    and (dotted(dec.func) or "").split(".")[-1]
                    == "partial" and dec.args
                    and _is_jit_expr(dec.args[0]))
                if direct or via_partial:
                    a.emit("jit-bypass-plan", mod, dec,
                           f"`{fi.qualname}` is jit-decorated in the "
                           "EC dispatch layer, bypassing the ExecPlan "
                           "cache (shape-polymorphic entry points "
                           "retrace per shape) — route through "
                           "ceph_tpu.ec.plan",
                           severity="warning", symbol=fi.qualname,
                           scope_line=fi.lineno)


# ---------------------------------------------------------------------
# unguarded-device-dispatch
# ---------------------------------------------------------------------

# modules whose device dispatches must route through the breaker guard
# (ceph_tpu/common/circuit.py device_call): ec/, ops/ and osd/ host
# the production data path — a raw dispatch there turns a device fault
# into a client-visible error instead of a host-path degrade
_DEVICE_DISPATCH_PATHS = ("ceph_tpu/ec/", "ceph_tpu/ops/",
                          "ceph_tpu/osd/")
# callee identities that ARE device dispatches: the mesh pipeline
# entry, the single-device XLA kernel, and the pallas word kernels
_DEVICE_ENTRY_TAILS = {"gf_matmul_tpu", "gf_matmul_words",
                       "gf_matmul_words_runtime"}
_DEVICE_ENTRY_SUFFIXES = (".backend.matmul",)


def _inside_device_call(mod, node: ast.AST) -> bool:
    """True when the call is lexically inside an argument of a
    `device_call(...)` invocation (the guard receives it as the
    supervised body) — that IS the guarded form."""
    cur = node
    while cur is not None:
        cur = mod.parents.get(cur)
        if isinstance(cur, ast.Call) and \
                (dotted(cur.func) or "").split(".")[-1] == \
                "device_call":
            return True
    return False


def rule_unguarded_device_dispatch(a: Analyzer) -> None:
    """Raw device dispatch outside circuit.device_call in the data-
    path modules: no watchdog, no breaker accounting, no injection
    seam, and a device exception propagates to the caller.  Route the
    call through the guard (or baseline with a justification — the
    guard's own internals legitimately dispatch raw)."""
    paths = a.config.get("device_paths", _DEVICE_DISPATCH_PATHS)
    for mod in a.project.modules.values():
        rel = mod.relpath.replace("\\", "/")
        if not any(p in rel for p in paths):
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _resolved_callee(mod, node)
            if not callee:
                continue
            hit = (callee.split(".")[-1] in _DEVICE_ENTRY_TAILS
                   or callee.endswith(_DEVICE_ENTRY_SUFFIXES))
            if hit and not _inside_device_call(mod, node):
                a.emit("unguarded-device-dispatch", mod, node,
                       f"raw device dispatch `{callee}` outside the "
                       "breaker guard: a wedged/faulting accelerator "
                       "raises here instead of degrading to the host "
                       "path — route through "
                       "ceph_tpu.common.circuit.device_call",
                       severity="warning",
                       symbol=_enclosing_qualname(mod, node),
                       scope_line=_scope_line(mod, node))


# ---------------------------------------------------------------------
# unplanned-mesh-dispatch
# ---------------------------------------------------------------------

# modules whose multi-chip compiles must ride the plan cache: a raw
# shard_map/pjit in the data path compiles outside plan.stats()
# (retraces invisible), binds whatever device set exists at build
# time (a dead chip's mesh is never retired), and dispatches outside
# the breaker guard (no watchdog, no sick-chip attribution)
_MESH_DISPATCH_PATHS = ("ceph_tpu/ec/", "ceph_tpu/osd/",
                        "ceph_tpu/parallel/")
_MESH_ENTRY_TAILS = {"shard_map", "pjit"}


def _inside_tracked_jit(mod, node: ast.AST) -> bool:
    """True when the call is lexically inside an argument of a
    `tracked_jit(...)` invocation — the compile lands in the plan
    cache's retrace counters, that IS the planned form."""
    cur = node
    while cur is not None:
        cur = mod.parents.get(cur)
        if isinstance(cur, ast.Call) and \
                (dotted(cur.func) or "").split(".")[-1] == \
                "tracked_jit":
            return True
    return False


def rule_unplanned_mesh_dispatch(a: Analyzer) -> None:
    """Raw shard_map/pjit in ec/, osd/, parallel/ bypassing the plan
    cache and the breaker guard: route the compiled callable through
    plan.tracked_jit (or a plan kind keyed on the mesh signature, so
    a shrunken healthy set retires the stale executable), and the
    dispatch through circuit.device_call.  The striped.py internals
    that legitimately sit UNDER the plan builders are baselined with
    justifications."""
    paths = a.config.get("mesh_paths", _MESH_DISPATCH_PATHS)
    for mod in a.project.modules.values():
        rel = mod.relpath.replace("\\", "/")
        if not any(p in rel for p in paths):
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _resolved_callee(mod, node)
            if not callee or \
                    callee.split(".")[-1] not in _MESH_ENTRY_TAILS:
                continue
            if _inside_tracked_jit(mod, node) or \
                    _inside_device_call(mod, node):
                continue
            a.emit("unplanned-mesh-dispatch", mod, node,
                   f"raw mesh compile `{callee}` outside the plan "
                   "cache: the XLA trace is invisible to "
                   "plan.stats(), the executable binds a device set "
                   "no health shrink can retire, and the dispatch "
                   "skips the breaker guard — wrap with "
                   "ceph_tpu.ec.plan.tracked_jit (or a mesh plan "
                   "kind) and dispatch via circuit.device_call",
                   severity="warning",
                   symbol=_enclosing_qualname(mod, node),
                   scope_line=_scope_line(mod, node))


# ---------------------------------------------------------------------
# unplanned-compute-dispatch
# ---------------------------------------------------------------------

# modules whose coded-compute kernel evaluations must ride the plan
# cache: `compute.kernels.make_device_eval` builds the one traced
# kernel body, and a raw invocation compiles outside plan.stats()
# (retraces invisible) and dispatches outside the breaker guard (no
# watchdog, no host fallback — a wedged accelerator stalls the scan
# instead of degrading it)
_COMPUTE_DISPATCH_PATHS = ("ceph_tpu/compute/", "ceph_tpu/osd/")
_COMPUTE_ENTRY_TAILS = {"device_eval", "make_device_eval"}


def rule_unplanned_compute_dispatch(a: Analyzer) -> None:
    """Raw compute-kernel device invocation in compute//osd/ outside
    the plan cache / breaker guard: route wave evaluations through
    ceph_tpu.ec.plan.compute_eval (the `compute` plan kind —
    tracked_jit + quarantine + the `compute` breaker family) or wrap
    the dispatch in circuit.device_call.  The bit-exact numpy twin
    (`host_eval`) is the legitimate raw path."""
    paths = a.config.get("compute_paths", _COMPUTE_DISPATCH_PATHS)
    for mod in a.project.modules.values():
        rel = mod.relpath.replace("\\", "/")
        if not any(p in rel for p in paths):
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _resolved_callee(mod, node) or \
                dotted(node.func) or ""
            if callee.split(".")[-1] not in _COMPUTE_ENTRY_TAILS:
                continue
            if _inside_tracked_jit(mod, node) or \
                    _inside_device_call(mod, node):
                continue
            a.emit("unplanned-compute-dispatch", mod, node,
                   f"raw compute-kernel dispatch `{callee}` outside "
                   "the plan cache: the XLA trace is invisible to "
                   "plan.stats() and the dispatch skips the breaker "
                   "guard (no watchdog, no bit-exact host "
                   "degradation) — route through "
                   "ceph_tpu.ec.plan.compute_eval or wrap with "
                   "circuit.device_call",
                   severity="warning",
                   symbol=_enclosing_qualname(mod, node),
                   scope_line=_scope_line(mod, node))


# ---------------------------------------------------------------------
# unscheduled-bitmatrix-xor
# ---------------------------------------------------------------------

# modules whose XOR region programs must ride the schedule compiler
# (ceph_tpu/ec/xsched.py): a hand-rolled row walk pays the naive XOR
# count (no CSE), compiles nothing (no memoization), never reaches
# the native fused-tape executor (xsched.execute_native) and is
# invisible to plan.stats()["xsched"].  The OSD data path
# (ceph_tpu/osd/) is covered too — its encode/recovery folds are the
# hot small-op band that the native executor exists for.  xsched.py
# holds the kill-switch naive walk itself and plan.py the device
# lowering — the two legitimate homes; osdmap.py XORs scalar state
# flag words, not byte regions.
_XSCHED_PATHS = ("ceph_tpu/ec/", "ceph_tpu/osd/")
_XSCHED_EXEMPT = ("ec/xsched.py", "ec/plan.py", "osd/osdmap.py")
# GF-multiply callee tails: a loop that MULTIPLIES (the wide-word
# GF(2^16/32) host matmul) is field math, not a schedulable pure-XOR
# walk
_GF_MUL_TAILS = {"mul", "mul_vec", "gf_mul", "gf_mul_jax"}


def _enclosing_loops(mod, node: ast.AST) -> list:
    out = []
    cur = node
    while cur is not None:
        cur = mod.parents.get(cur)
        if isinstance(cur, (ast.For, ast.While, ast.AsyncFor)):
            out.append(cur)
    return out


def _loop_multiplies(loops: list) -> bool:
    for loop in loops:
        for sub in ast.walk(loop):
            if isinstance(sub, ast.Call) and \
                    (dotted(sub.func) or "").split(".")[-1] in \
                    _GF_MUL_TAILS:
                return True
    return False


def rule_unscheduled_bitmatrix_xor(a: Analyzer) -> None:
    """Naive bitmatrix row-walk in ec/ or osd/ outside xsched/plan:
    a loop XOR-folding byte regions (`np.bitwise_xor.reduce(...)` or
    a subscripted `^=` accumulate) re-pays the naive XOR count on
    every call and never reaches the native fused tape — compile the
    matrix once (xsched.compile_matrix, memoized by sha256
    signature) and run the schedule through the execute seam
    (xsched.execute, which picks execute_native when the runtime is
    built and falls back to execute_host).  Pure-XOR loops only: loops that also GF-multiply
    (wide-word fields) are exempt."""
    paths = a.config.get("xsched_paths", _XSCHED_PATHS)
    exempt = a.config.get("xsched_exempt", _XSCHED_EXEMPT)
    for mod in a.project.modules.values():
        rel = mod.relpath.replace("\\", "/")
        if not any(p in rel for p in paths):
            continue
        if any(e in rel for e in exempt):
            continue
        for node in ast.walk(mod.tree):
            what = None
            if isinstance(node, ast.Call) and \
                    (dotted(node.func) or "").endswith(
                        "bitwise_xor.reduce"):
                what = "np.bitwise_xor.reduce row-fold"
            elif isinstance(node, ast.AugAssign) and \
                    isinstance(node.op, ast.BitXor) and \
                    isinstance(node.target, ast.Subscript):
                what = "subscripted ^= XOR accumulation"
            if what is None:
                continue
            loops = _enclosing_loops(mod, node)
            if not loops or _loop_multiplies(loops):
                continue
            a.emit("unscheduled-bitmatrix-xor", mod, node,
                   f"{what} inside a loop: a naive row walk pays "
                   "the unoptimized XOR count on every call, "
                   "compiles nothing and bypasses the native fused "
                   "tape — compile the bit matrix once "
                   "(ceph_tpu.ec.xsched.compile_matrix, memoized by "
                   "signature) and run it through the execute seam "
                   "(xsched.execute: native single-dispatch tape "
                   "when built, execute_host fallback)",
                   severity="warning",
                   symbol=_enclosing_qualname(mod, node),
                   scope_line=_scope_line(mod, node))


# ---------------------------------------------------------------------
# raw-process-group
# ---------------------------------------------------------------------

# the bootstrap seam: the ONE module allowed to join or configure the
# jax.distributed process group (it selects the CPU collectives, owns
# the host-topology map, and keeps membership agreement
# collective-safe); everywhere else a raw initialize builds a group
# the failure-domain machinery cannot see
_PROCGROUP_EXEMPT = ("parallel/multihost.py",)
_PROCGROUP_TAILS = {"initialize", "shutdown"}


def rule_raw_process_group(a: Analyzer) -> None:
    """Raw ``jax.distributed.initialize`` / process-group setup
    outside the parallel/multihost.py bootstrap seam.  The seam is
    load-bearing: it configures the CPU collectives BEFORE backend
    init, feeds the host failure-domain topology (``host:<id>``
    breakers, the plan keys' process-topology element), and keeps
    membership agreement on the coordinator KV store instead of a
    collective a dead host would wedge.  Route group setup through
    ``multihost.initialize()`` / ``bootstrap_from_env()``."""
    exempt = a.config.get("procgroup_exempt", _PROCGROUP_EXEMPT)
    for mod in a.project.modules.values():
        rel = mod.relpath.replace("\\", "/")
        if any(p in rel for p in exempt):
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _resolved_callee(mod, node) or \
                dotted(node.func) or ""
            parts = callee.split(".")
            if len(parts) >= 2 and parts[-2] == "distributed" \
                    and parts[-1] in _PROCGROUP_TAILS:
                a.emit("raw-process-group", mod, node,
                       f"raw process-group setup `{callee}` outside "
                       "the parallel/multihost.py bootstrap seam: "
                       "the group skips the collectives config, the "
                       "host-topology map, topology-aware plan keys "
                       "and collective-safe membership agreement — "
                       "call ceph_tpu.parallel.multihost.initialize"
                       "() instead",
                       severity="warning",
                       symbol=_enclosing_qualname(mod, node),
                       scope_line=_scope_line(mod, node))


# ---------------------------------------------------------------------
# unhedged-gather
# ---------------------------------------------------------------------

# OSD modules whose sub-read/sub-write fan-outs are judged; the hedge
# primitive itself legitimately gathers (its cancellation drain)
_GATHER_PATHS = ("ceph_tpu/osd/",)
_GATHER_EXEMPT = ("osd/hedge.py",)
# names that mark a function as fanning out shard sub-ops: the
# sub-read job maker, the request primitive, and the sub-op messages
_SUBOP_MARKERS = {"_read_candidates", "_request", "MOSDSubRead",
                  "MOSDSubWrite"}


def _scope_subop_markers(mod, root: ast.AST) -> bool:
    for node in walk_scope(root):
        if isinstance(node, ast.Name) and node.id in _SUBOP_MARKERS:
            return True
        if isinstance(node, ast.Attribute) and \
                node.attr in _SUBOP_MARKERS:
            return True
    return False


def rule_unhedged_gather(a: Analyzer) -> None:
    """Bare `asyncio.gather` over shard sub-op jobs under ceph_tpu/osd/
    outside the hedge primitive (osd/hedge.py HedgeTracker.gather):
    the gather inherits the SLOWEST peer's latency — one degraded OSD
    sets p99 for every read through it — and its tasks are neither
    ranked by the per-peer EWMAs nor cancellation-managed.  Read-side
    fan-outs route through `self.hedge.gather`; write-path and
    absence-proof gathers that MUST stay all-shard (every shard must
    ack / every source must answer) are baselined with
    justifications."""
    paths = a.config.get("gather_paths", _GATHER_PATHS)
    exempt = a.config.get("gather_exempt", _GATHER_EXEMPT)
    for mod in a.project.modules.values():
        rel = mod.relpath.replace("\\", "/")
        if not any(p in rel for p in paths):
            continue
        if any(e in rel for e in exempt):
            continue
        for fi in mod.functions.values():
            if not fi.is_async:
                continue
            if not _scope_subop_markers(mod, fi.node):
                continue
            for node in walk_scope(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                if _resolved_callee(mod, node) != "asyncio.gather":
                    continue
                a.emit("unhedged-gather", mod, node,
                       f"bare asyncio.gather over shard sub-ops in "
                       f"`{fi.qualname}` completes at the SLOWEST "
                       "peer's pace and leaves tasks unmanaged — "
                       "route read fan-outs through the hedged "
                       "first-k primitive (osd/hedge.py "
                       "HedgeTracker.gather), or baseline all-shard "
                       "write/absence gathers with a justification",
                       severity="warning", symbol=fi.qualname,
                       scope_line=fi.lineno)


# ---------------------------------------------------------------------
# span-leak
# ---------------------------------------------------------------------


def _span_finally_names(fi_node: ast.AST) -> Set[str]:
    """Names referenced anywhere in a try/finally's finalbody within
    this function: a span passed (or receiver'd) there is finished on
    every path — `self.tracer.finish(span)`, `span.finish()`, and
    helper calls like `self._finish_op_span(span, op)` all count."""
    names: Set[str] = set()
    for node in walk_scope(fi_node):
        if isinstance(node, ast.Try) and node.finalbody:
            for stmt in node.finalbody:
                for sub in ast.walk(stmt):
                    if isinstance(sub, ast.Name):
                        names.add(sub.id)
    return names


def rule_span_leak(a: Analyzer) -> None:
    """`<...>.tracer.start(...)` (or bare `tracer.start(...)`) whose
    span does not provably finish on every path: the span must either
    be passed straight into a `.finish(...)` call, or be bound to a
    name that a try/finally in the same function references.  A leaked
    span is invisible — it never reaches the dump_traces ring, the
    critical-path stage histograms, or the tail-exemplar retention —
    and on an exception path it silently drops the one op most worth
    explaining.  The idiomatic fix is the context-manager surface:
    `async with tracer.span(...)` / `tracing.child_span(...)`."""
    for mod in a.project.modules.values():
        for fi in mod.functions.values():
            finally_names: Optional[Set[str]] = None
            parents: Optional[Dict[ast.AST, ast.AST]] = None
            for node in walk_scope(fi.node):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "start"):
                    continue
                recv = node.func.value
                if not ((isinstance(recv, ast.Name)
                         and recv.id == "tracer")
                        or (isinstance(recv, ast.Attribute)
                            and recv.attr == "tracer")):
                    continue
                if parents is None:
                    parents = {c: p for p in ast.walk(fi.node)
                               for c in ast.iter_child_nodes(p)}
                # walk up: directly consumed by a .finish(...) call?
                # bound to a name?  (conditional expressions and
                # boolop fallbacks still resolve to their Assign)
                cur = node
                bound: Optional[str] = None
                safe = False
                while cur in parents:
                    up = parents[cur]
                    if isinstance(up, ast.Call) and \
                            isinstance(up.func, ast.Attribute) and \
                            up.func.attr == "finish" and \
                            cur in up.args:
                        safe = True  # t.finish(t.start(...))
                        break
                    if isinstance(up, ast.Assign) and \
                            len(up.targets) == 1 and \
                            isinstance(up.targets[0], ast.Name):
                        bound = up.targets[0].id
                        break
                    if isinstance(up, (ast.stmt, ast.ExceptHandler)):
                        break
                    cur = up
                if safe:
                    continue
                if bound is not None:
                    if finally_names is None:
                        finally_names = _span_finally_names(fi.node)
                    if bound in finally_names:
                        continue
                a.emit("span-leak", mod, node,
                       f"span started in `{fi.qualname}` is not"
                       " finished in a finally/context-manager on"
                       " every path — an exception (or early return)"
                       " leaks it out of the trace ring, the stage"
                       " histograms and the tail exemplars; use"
                       " `async with tracer.span(...)` /"
                       " `tracing.child_span(...)`, or finish the"
                       " bound span in a try/finally",
                       severity="warning",
                       symbol=fi.qualname,
                       scope_line=fi.lineno)


# ---------------------------------------------------------------------
# sync-encode-in-async
# ---------------------------------------------------------------------

# OSD daemon modules whose async bodies must route EC encodes through
# the awaited encode service (osd/encode_service.py): a direct call
# blocks the event loop for the whole dispatch AND forfeits the
# micro-batching that folds concurrent writes into one device call.
_ENCODE_PATHS = ("ceph_tpu/osd/",)
# receiver names that denote an erasure codec in this codebase (the
# heuristic keeps str.encode()/json encode noise out of the findings)
_CODEC_RECEIVERS = {"codec", "ec_impl"}
_CODEC_ENCODE_ATTRS = {"encode", "encode_chunks", "encode_batch",
                       "encode_batch_with_crc", "encode_many",
                       "encode_many_with_crc"}


def rule_sync_encode_in_async(a: Analyzer) -> None:
    """Direct `ec_util.encode*` (or `codec.encode*(...)`) inside an
    `async def` under ceph_tpu/osd/: the EC encode runs synchronously
    on the daemon's event loop instead of awaiting the batching
    encode service.  Intentional inline fallbacks (the service's own
    degraded path) are baselined with justifications."""
    paths = a.config.get("encode_paths", _ENCODE_PATHS)
    for mod in a.project.modules.values():
        rel = mod.relpath.replace("\\", "/")
        if not any(p in rel for p in paths):
            continue
        for fi in mod.functions.values():
            if not fi.is_async:
                continue
            for node in walk_scope(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                callee = _resolved_callee(mod, node)
                util_encode = ".ec_util.encode" in f".{callee}"
                codec_encode = (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in _CODEC_ENCODE_ATTRS
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in _CODEC_RECEIVERS)
                if util_encode or codec_encode:
                    what = callee if util_encode else \
                        f"{node.func.value.id}.{node.func.attr}"
                    a.emit("sync-encode-in-async", mod, node,
                           f"synchronous EC encode `{what}` in "
                           f"`async def {fi.qualname}` runs on the "
                           "event loop and bypasses the micro-"
                           "batching encode service — await "
                           "self.encode_service instead "
                           "(osd/encode_service.py)",
                           symbol=fi.qualname, scope_line=fi.lineno)


# ---------------------------------------------------------------------
# unbounded-latency-buffer
# ---------------------------------------------------------------------

# modules whose measurement loops are judged: the loadgen subsystem
# and the CLI bench tools (the paths where per-op sample buffers grow
# with offered load x duration)
_LATENCY_PATHS = ("ceph_tpu/loadgen/", "ceph_tpu/tools/")
# receiver names that denote a latency sample buffer
_LATENCY_NAME_RE = re.compile(
    r"lat|latenc|rtt|elapsed|duration|timing|sample")
# clock reads whose difference is a latency sample
_CLOCK_CALLS = {
    "time.monotonic", "time.perf_counter", "time.time",
    "time.monotonic_ns", "time.perf_counter_ns", "time.time_ns",
}


def _inside_loop(mod, node: ast.AST) -> bool:
    """True when the node sits inside a for/while of the SAME
    function scope (a nested def resets the judgment)."""
    cur = node
    while cur is not None:
        cur = mod.parents.get(cur)
        if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
            return True
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            return False
    return False


def _has_clock_call(mod, node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and \
                _resolved_callee(mod, sub) in _CLOCK_CALLS:
            return True
    return False


def rule_unbounded_latency_buffer(a: Analyzer) -> None:
    """`<buffer>.append(<per-op sample>)` inside a loadgen/bench
    loop: the list grows without bound under open-loop load (offered
    rate x duration samples, regardless of completions).  Stream the
    sample into ceph_tpu.loadgen.stats.LatencyHistogram (constant
    memory, same percentiles) or baseline a deliberately-bounded
    buffer with a justification."""
    paths = a.config.get("latency_paths", _LATENCY_PATHS)
    for mod in a.project.modules.values():
        rel = mod.relpath.replace("\\", "/")
        if not any(p in rel for p in paths):
            continue
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "append"
                    and node.args):
                continue
            recv = node.func.value
            recv_name = recv.id if isinstance(recv, ast.Name) else (
                recv.attr if isinstance(recv, ast.Attribute) else "")
            looks_latency = bool(
                _LATENCY_NAME_RE.search(recv_name.lower())) or \
                _has_clock_call(mod, node.args[0])
            if looks_latency and _inside_loop(mod, node):
                a.emit("unbounded-latency-buffer", mod, node,
                       f"per-op latency sample appended to "
                       f"`{recv_name or '<expr>'}` inside a bench "
                       "loop: under open-loop load this list grows "
                       "with offered rate x duration — stream into "
                       "ceph_tpu.loadgen.stats.LatencyHistogram "
                       "(bounded log buckets) instead",
                       severity="warning",
                       symbol=_enclosing_qualname(mod, node),
                       scope_line=_scope_line(mod, node))


# ---------------------------------------------------------------------
# unbudgeted-approx-result
# ---------------------------------------------------------------------

# modules whose approximate-combine returns must ride the error-budget
# gate (ceph_tpu/inference/fisher.py check_budget)
_APPROX_PATHS = ("ceph_tpu/inference/", "ceph_tpu/osd/inference")
# callee tails of the approximate step: solving missing shard
# contributions from fused results is what makes the output an
# ESTIMATE rather than the exact forward
_APPROX_SOLVER_TAILS = {"lstsq", "pinv", "solve"}
# callee tails / name fragments that synthesize final combined scores
_APPROX_COMBINE_TAILS = {"combine", "combine_contributions"}
_BUDGET_GATE = "check_budget"


def rule_unbudgeted_approx_result(a: Analyzer) -> None:
    """A function in the inference paths that both SOLVES missing
    shard contributions (lstsq/pinv — the approximate step) and
    synthesizes combined scores, yet returns without ever consulting
    fisher.check_budget: the result's estimated error was never
    priced against the caller's budget, so an out-of-budget
    approximation serves silently instead of falling back to the
    exact full-decode path.  Pure solver helpers (no combine) and
    exact paths (no solve) are not findings."""
    paths = a.config.get("approx_paths", _APPROX_PATHS)
    for mod in a.project.modules.values():
        rel = mod.relpath.replace("\\", "/")
        if not any(p in rel for p in paths):
            continue
        for fi in mod.functions.values():
            tails: Set[str] = set()
            for node in _scope_nodes(fi.node):
                if isinstance(node, ast.Call):
                    callee = _resolved_callee(mod, node) or \
                        dotted(node.func) or ""
                    tails.add(callee.split(".")[-1])
            if not tails & _APPROX_SOLVER_TAILS:
                continue
            combines = bool(tails & _APPROX_COMBINE_TAILS) or \
                "combine" in fi.node.name.lower() or \
                "approx" in fi.node.name.lower()
            if not combines or _BUDGET_GATE in tails:
                continue
            for node in _scope_nodes(fi.node):
                if not isinstance(node, ast.Return) or \
                        node.value is None or \
                        (isinstance(node.value, ast.Constant)
                         and node.value.value is None):
                    continue
                a.emit("unbudgeted-approx-result", mod, node,
                       f"`{fi.qualname}` returns an approximate "
                       "combine (least-squares solve of missing "
                       "shard contributions) without consulting "
                       "ceph_tpu.inference.fisher.check_budget: the "
                       "estimated error was never priced against "
                       "the caller's budget — gate the return on "
                       "check_budget(est, budget) or fall back to "
                       "the exact full-decode path",
                       severity="warning", symbol=fi.qualname,
                       scope_line=fi.lineno)


# ---------------------------------------------------------------------
# lock-no-await
# ---------------------------------------------------------------------

def _class_lock_attrs(project) -> Dict[str, Set[str]]:
    """class name -> asyncio-lock attrs it assigns, across modules."""
    out: Dict[str, Set[str]] = {}
    for mod in project.modules.values():
        for cls, attrs in mod.lock_attrs.items():
            out.setdefault(cls, set()).update(attrs)
    return out


def _is_lock_attr(mod, node: ast.AST, attr: str,
                  by_class: Dict[str, Set[str]]) -> bool:
    """True when `self.<attr>` resolves to an asyncio lock of the
    ENCLOSING class.  Name-keyed project-wide matching would turn a
    same-named threading.Lock in an unrelated class into a finding, so
    only `self.` accesses bindable to their class are judged."""
    cur: Optional[ast.AST] = node
    while cur is not None:
        cur = mod.parents.get(cur)
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for fi in mod.functions.values():
                if fi.node is cur:
                    return bool(fi.parent_class) and \
                        attr in by_class.get(fi.parent_class, ())
            return False
    return False


def rule_lock_no_await(a: Analyzer) -> None:
    by_class = _class_lock_attrs(a.project)
    for mod in a.project.modules.values():
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "acquire":
                base = node.func.value
                if isinstance(base, ast.Attribute) and \
                        isinstance(base.value, ast.Name) and \
                        base.value.id == "self" and \
                        _is_lock_attr(mod, node, base.attr, by_class) \
                        and not isinstance(
                            mod.parents.get(node), ast.Await):
                    sym = _enclosing_qualname(mod, node)
                    a.emit("lock-no-await", mod, node,
                           f"asyncio.Lock `{base.attr}`.acquire() "
                           "without await: returns a coroutine, the "
                           "lock is never taken",
                           symbol=sym,
                           scope_line=_scope_line(mod, node))
            elif isinstance(node, ast.With):
                for item in node.items:
                    expr = item.context_expr
                    if isinstance(expr, ast.Attribute) and \
                            isinstance(expr.value, ast.Name) and \
                            expr.value.id == "self" and \
                            _is_lock_attr(mod, node, expr.attr,
                                          by_class):
                        sym = _enclosing_qualname(mod, node)
                        a.emit("lock-no-await", mod, node,
                               f"sync `with` on asyncio.Lock "
                               f"`{expr.attr}`: needs `async with`",
                               symbol=sym,
                               scope_line=_scope_line(mod, node))


# ---------------------------------------------------------------------
# commit-before-durability
# ---------------------------------------------------------------------

# store modules whose commit callbacks are judged: firing `on_commit`
# before the durability point (block fsync / sync KV batch) acks a
# write a power cut can still lose — the one failure QoS, breakers and
# hedging cannot paper over
_DURABILITY_PATHS = ("ceph_tpu/os/",)
# calls that establish durability for everything before them
_DURABILITY_FSYNCS = {"os.fsync", "os.fdatasync"}
_DURABILITY_ATTRS = {"fsync", "fdatasync", "submit_transaction_sync",
                     "_block_sync"}


def _is_durability_call(mod, node: ast.Call) -> bool:
    if _resolved_callee(mod, node) in _DURABILITY_FSYNCS:
        return True
    return isinstance(node.func, ast.Attribute) and \
        node.func.attr in _DURABILITY_ATTRS


def rule_commit_before_durability(a: Analyzer) -> None:
    """`on_commit`/ack callbacks reachable before the store's
    durability point in ceph_tpu/os/: a `for cb in txn.on_commit:
    cb()` loop with no fsync / `submit_transaction_sync` /
    `_block_sync` lexically ahead of it acks a transaction that a
    power cut can still erase.  The MemStore no-durability path is
    intentional and baselined with a justification."""
    paths = a.config.get("durability_paths", _DURABILITY_PATHS)
    for mod in a.project.modules.values():
        rel = mod.relpath.replace("\\", "/")
        if not any(p in rel for p in paths):
            continue
        for fi in mod.functions.values():
            durable_lines = [
                node.lineno for node in walk_scope(fi.node)
                if isinstance(node, ast.Call)
                and _is_durability_call(mod, node)]
            for node in walk_scope(fi.node):
                if not isinstance(node, ast.For):
                    continue
                # `for cb in <expr>.on_commit:` (incl. list(...) wraps)
                iter_attrs = {sub.attr for sub in ast.walk(node.iter)
                              if isinstance(sub, ast.Attribute)}
                if "on_commit" not in iter_attrs or \
                        not isinstance(node.target, ast.Name):
                    continue
                cb = node.target.id
                for sub in ast.walk(node):
                    if not (isinstance(sub, ast.Call)
                            and isinstance(sub.func, ast.Name)
                            and sub.func.id == cb):
                        continue
                    if not any(dl < sub.lineno
                               for dl in durable_lines):
                        a.emit(
                            "commit-before-durability", mod, sub,
                            f"`{fi.qualname}` fires on_commit with no"
                            " durability point (fsync /"
                            " submit_transaction_sync / _block_sync)"
                            " ahead of it — the acked transaction can"
                            " vanish on power loss; commit the KV"
                            " batch sync (or fsync the data) before"
                            " acking, or baseline an intentional"
                            " no-durability store with a"
                            " justification",
                            severity="error", symbol=fi.qualname,
                            scope_line=fi.lineno)



# ---------------------------------------------------------------------
# unregistered-kill-switch
# ---------------------------------------------------------------------

# the one module allowed to touch os.environ with CEPH_TPU_ literals:
# the kill-switch registry itself
_KILL_SWITCH_REGISTRY_PATHS = ("common/flags.py",)
# environ accessors whose literal first argument is a flag read/write
_ENVIRON_METHODS = {"get", "getenv", "setdefault", "pop"}


def _mentions_environ(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr == "environ":
            return True
        if isinstance(sub, ast.Name) and sub.id == "environ":
            return True
    return False


def _kill_switch_key(node: ast.AST) -> Optional[str]:
    """The CEPH_TPU_* literal this node reads/writes straight off the
    process environment, or None."""

    def lit(e):
        if isinstance(e, ast.Constant) and isinstance(e.value, str) \
                and e.value.startswith("CEPH_TPU_"):
            return e.value
        return None

    if isinstance(node, ast.Call) and \
            isinstance(node.func, ast.Attribute) and node.args:
        # os.environ.get/setdefault/pop("CEPH_TPU_X"), os.getenv(...)
        if node.func.attr == "getenv" or (
                node.func.attr in _ENVIRON_METHODS
                and _mentions_environ(node.func.value)):
            return lit(node.args[0])
    if isinstance(node, ast.Subscript) and \
            _mentions_environ(node.value):
        # os.environ["CEPH_TPU_X"] — read or assignment
        return lit(node.slice)
    if isinstance(node, ast.Compare) and \
            isinstance(node.ops[0], (ast.In, ast.NotIn)) and \
            _mentions_environ(node.comparators[0]):
        # "CEPH_TPU_X" in os.environ
        return lit(node.left)
    return None


def rule_unregistered_kill_switch(a: Analyzer) -> None:
    """Raw ``os.environ`` access with a ``CEPH_TPU_*`` literal outside
    ``common/flags.py``: the switch is invisible to the registry — no
    declared default/scope, no live-flip hook, no audit trail for the
    chaos engine's kill-switch hazard — and its per-site default
    string can drift.  Route reads through ``flags.get`` /
    ``flags.enabled`` / ``flags.flag_float`` / ``flags.flag_int`` and
    writes through ``flags.set_flag`` / ``flags.clear`` /
    ``flags.setdefault``, registering the flag in the table."""
    exempt = a.config.get("kill_switch_registry_paths",
                          _KILL_SWITCH_REGISTRY_PATHS)
    for mod in a.project.modules.values():
        rel = mod.relpath.replace("\\", "/")
        if any(p in rel for p in exempt):
            continue
        for node in ast.walk(mod.tree):
            key = _kill_switch_key(node)
            if key is None:
                continue
            a.emit(
                "unregistered-kill-switch", mod, node,
                f"raw os.environ access of `{key}` bypasses the "
                "kill-switch registry (ceph_tpu/common/flags.py): "
                "no declared default/scope, no live-flip hook, no "
                "audit for chaos kill-switch flips — use "
                "flags.get/enabled/flag_float/flag_int (reads) or "
                "flags.set_flag/clear/setdefault (writes) and "
                "register the flag",
                severity="error",
                symbol=_enclosing_qualname(mod, node),
                scope_line=_scope_line(mod, node))


def default_rules() -> Dict[str, object]:
    # lock-order lives in lockgraph.py (it needs the whole-project
    # graph) and the interprocedural async rules in rules_async.py
    # (they need the callgraph.py layer); imported here to keep one
    # registry.  unused-suppression MUST run last: it audits the
    # suppression-hit ledger every earlier rule's emit() fills.
    from ceph_tpu.analysis.lockgraph import rule_lock_order
    from ceph_tpu.analysis.rules_async import (
        rule_await_atomicity, rule_cancellation_unsafe_acquire,
        rule_hot_path_copy, rule_transitive_blocking_call,
        rule_unused_suppression,
    )
    from ceph_tpu.analysis.rules_spmd import (
        rule_collective_order, rule_divergent_collective,
        rule_topology_stale_state, rule_unguarded_collective_timeout,
    )
    return {
        "trace-side-effect": rule_trace_side_effect,
        "trace-host-sync": rule_trace_host_sync,
        "uint8-overflow": rule_uint8_overflow,
        "trace-static-hazard": rule_trace_static_hazard,
        "trace-numpy": rule_trace_numpy,
        "jit-bypass-plan": rule_jit_bypass_plan,
        "unguarded-device-dispatch": rule_unguarded_device_dispatch,
        "unplanned-mesh-dispatch": rule_unplanned_mesh_dispatch,
        "unplanned-compute-dispatch": rule_unplanned_compute_dispatch,
        "unscheduled-bitmatrix-xor": rule_unscheduled_bitmatrix_xor,
        "raw-process-group": rule_raw_process_group,
        "unhedged-gather": rule_unhedged_gather,
        "span-leak": rule_span_leak,
        "unbounded-latency-buffer": rule_unbounded_latency_buffer,
        "unbudgeted-approx-result": rule_unbudgeted_approx_result,
        "commit-before-durability": rule_commit_before_durability,
        "unregistered-kill-switch": rule_unregistered_kill_switch,
        "async-blocking": rule_async_blocking,
        "sync-encode-in-async": rule_sync_encode_in_async,
        "lock-order": rule_lock_order,
        "lock-no-await": rule_lock_no_await,
        "await-atomicity": rule_await_atomicity,
        "cancellation-unsafe-acquire": rule_cancellation_unsafe_acquire,
        "transitive-blocking-call": rule_transitive_blocking_call,
        "hot-path-copy": rule_hot_path_copy,
        "divergent-collective": rule_divergent_collective,
        "collective-order": rule_collective_order,
        "unguarded-collective-timeout":
            rule_unguarded_collective_timeout,
        "topology-stale-state": rule_topology_stale_state,
        "unused-suppression": rule_unused_suppression,
    }
