"""The tmlint rule set: 9 project invariants as AST checks.

Each rule is a pure function Project -> [Finding], registered under the
name used in output, pragmas, and --rule. The concurrency rules share one
whole-project lock/function model (built once per run) so the lock-order
graph can follow calls across modules.

Rules (docs/LINT.md has the full table with the motivating PR trail):

  lock-held-call          no blocking/callback calls under a held lock
  lock-order              static lock-acquisition graph must be acyclic
  device-sync-choke-point jax.device_get & friends only at audited sites
  thread-crash-surface    thread targets need a broad try/except shield
  daemon-or-joined        every Thread is daemonized or tracked for join
  metrics-discipline      labeled counters/gauges pre-seeded or removal-
                          disciplined (bounded exposition)
  fault-site-registry     faults.fire(...) literals canonical + documented
  trace-span-discipline   trace span(...) names canonical + documented
  config-knob-parity      TM_TPU_*/TMTPU_* knobs <-> docs/CONFIG.md
"""

from __future__ import annotations

import ast
import os
import re

from tools.tmlint.core import Finding, Project, rule

# ---------------------------------------------------------------------------
# AST helpers
# ---------------------------------------------------------------------------


def dotted(node) -> str | None:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def terminal(node) -> str | None:
    """Last segment of a call target ('c' for a.b.c(...))."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _kwarg(call: ast.Call, name: str):
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


_LOCK_SEG = re.compile(r"(?:^|_)(?:lock|mtx|mu|cv|cond)\d*$")


def _lockish_name(name: str) -> bool:
    return bool(_LOCK_SEG.search(name))


def _short_module(path: str) -> str:
    """tendermint_tpu/p2p/switch.py -> p2p.switch (message-sized keys)."""
    p = path[:-3] if path.endswith(".py") else path
    parts = p.split("/")
    if parts and parts[0] == "tendermint_tpu":
        parts = parts[1:]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts) or p


_LOCK_CTORS = {"Lock", "RLock", "Condition"}


# ---------------------------------------------------------------------------
# Whole-project lock / function model
# ---------------------------------------------------------------------------


class FuncInfo:
    def __init__(self, key, module, cls, node, path):
        self.key = key          # "p2p.switch:Switch.dial_peer"
        self.module = module
        self.cls = cls          # enclosing class name or None
        self.node = node
        self.path = path
        self.acquires: list = []       # (lockkey|None, rawtext, line)
        self.edges: list = []          # (lockA, lockB, path, line)
        self.calls_under: list = []    # (ref, heldkeys, innermost_raw, line)
        self.calls_all: list = []      # refs
        self.blocking: list = []       # (callname, lockraw, line)
        self.thread_spawns: list = []  # ast.Call nodes of threading.Thread(...)


class LockModel:
    """Pass 1 collects classes/functions/imports/lock attributes; pass 2
    scans every function body resolving lock identities and call refs."""

    def __init__(self, project: Project):
        self.project = project
        self.class_locks: dict = {}    # (mod, cls) -> {attr: kind}
        self.module_locks: dict = {}   # mod -> {name: kind}
        self.methods: dict = {}        # (mod, cls) -> {name: funckey}
        self.module_funcs: dict = {}   # mod -> {name: funckey}
        self.imports: dict = {}        # mod -> {alias: target mod (short)}
        self.from_funcs: dict = {}     # mod -> {alias: (target mod, name)}
        self.funcs: dict = {}          # funckey -> FuncInfo
        self._attr_owner: dict = {}    # lock attr -> set of (mod, cls)
        self._method_owner: dict = {}  # method name -> set of funckey
        self._build()
        self._scan_all()
        self.may_acquire = self._closure()

    # -- pass 1 -------------------------------------------------------------

    def _build(self) -> None:
        for sf in self.project.prod_files():
            mod = _short_module(sf.path)
            self.imports.setdefault(mod, {})
            self.from_funcs.setdefault(mod, {})
            self.module_locks.setdefault(mod, {})
            self.module_funcs.setdefault(mod, {})
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.Import):
                    for a in node.names:
                        if a.name.startswith("tendermint_tpu"):
                            short = ".".join(a.name.split(".")[1:]) or a.name
                            self.imports[mod][a.asname or a.name.split(".")[-1]] = short
                elif isinstance(node, ast.ImportFrom):
                    if node.module and node.module.startswith("tendermint_tpu"):
                        base = ".".join(node.module.split(".")[1:])
                        for a in node.names:
                            # `from tendermint_tpu.utils import faults` makes
                            # faults a module alias; `from ..utils.faults
                            # import fire` a function alias. Record both ways;
                            # resolution tries module first.
                            tgt = f"{base}.{a.name}" if base else a.name
                            self.imports[mod].setdefault(a.asname or a.name, tgt)
                            if base:
                                self.from_funcs[mod].setdefault(
                                    a.asname or a.name, (base, a.name))
            self._collect_defs(sf, mod)

    def _collect_defs(self, sf, mod: str) -> None:
        def walk(body, cls, prefix):
            for node in body:
                if isinstance(node, ast.ClassDef):
                    self.methods.setdefault((mod, node.name), {})
                    self.class_locks.setdefault((mod, node.name), {})
                    walk(node.body, node.name, prefix + node.name + ".")
                elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    key = f"{mod}:{prefix}{node.name}"
                    info = FuncInfo(key, mod, cls, node, sf.path)
                    self.funcs[key] = info
                    if cls is not None and prefix == cls + ".":
                        self.methods[(mod, cls)][node.name] = key
                        self._method_owner.setdefault(node.name, set()).add(key)
                    elif cls is None and not prefix:
                        self.module_funcs[mod][node.name] = key
                    # nested defs get their own FuncInfo (thread targets)
                    walk(node.body, cls, prefix + node.name + ".")
                else:
                    if isinstance(node, ast.Assign) and not prefix:
                        self._note_lock_assign(node, mod, None)
                    # defs directly under module-level if/try blocks
                    walk([c for c in ast.iter_child_nodes(node)
                          if isinstance(c, (ast.ClassDef, ast.FunctionDef,
                                            ast.AsyncFunctionDef))],
                         cls, prefix)

        walk(sf.tree.body, None, "")
        # method bodies: lock attribute assignments + `with self.X` usage
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.ClassDef):
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Assign):
                        self._note_lock_assign(sub, mod, node.name)
                    elif isinstance(sub, ast.With):
                        for item in sub.items:
                            d = dotted(item.context_expr)
                            if (d and d.startswith("self.")
                                    and d.count(".") == 1
                                    and _lockish_name(d.split(".")[1])):
                                self.class_locks.setdefault(
                                    (mod, node.name), {}).setdefault(
                                    d.split(".")[1], "?")

        for (m, c), attrs in self.class_locks.items():
            for a in attrs:
                self._attr_owner.setdefault(a, set()).add((m, c))

    def _note_lock_assign(self, node: ast.Assign, mod, cls) -> None:
        if not isinstance(node.value, ast.Call):
            return
        t = terminal(node.value.func)
        d = dotted(node.value.func) or ""
        if t not in _LOCK_CTORS or not (d.startswith("threading.") or d == t):
            return
        for tgt in node.targets:
            td = dotted(tgt)
            if td is None:
                continue
            if td.startswith("self.") and td.count(".") == 1 and cls:
                self.class_locks.setdefault((mod, cls), {})[td[5:]] = t
            elif "." not in td and cls is None:
                self.module_locks.setdefault(mod, {})[td] = t

    # -- lock identity ------------------------------------------------------

    def lock_key(self, expr, mod: str, cls: str | None) -> str | None:
        """Stable identity for a lock expression, or None when the owner
        cannot be pinned (region still tracked, no order edges)."""
        d = dotted(expr)
        if d is None:
            return None
        seg = d.split(".")[-1]
        if d.startswith("self.") and d.count(".") == 1 and cls is not None:
            if _lockish_name(seg) or seg in self.class_locks.get((mod, cls), {}):
                self.class_locks.setdefault((mod, cls), {}).setdefault(seg, "?")
                return f"{mod}.{cls}.{seg}"
            return None
        if "." not in d:
            if d in self.module_locks.get(mod, {}):
                return f"{mod}.{d}"
            return None  # local variable: instance unknowable statically
        # obj.X / self.a.X: resolvable iff exactly one class owns lock X
        owners = self._attr_owner.get(seg)
        if owners and len(owners) == 1:
            (m, c), = owners
            return f"{m}.{c}.{seg}"
        return None

    def lock_kind(self, key: str) -> str:
        mod_cls, _, attr = key.rpartition(".")
        mod, _, cls = mod_cls.rpartition(".")
        for (m, c), attrs in self.class_locks.items():
            if f"{m}.{c}" == mod_cls:
                return attrs.get(attr, "?")
        return self.module_locks.get(mod_cls, {}).get(attr, "?")

    def _is_lockish_expr(self, expr, mod, cls) -> bool:
        d = dotted(expr)
        if d is None:
            return False
        seg = d.split(".")[-1]
        if _lockish_name(seg):
            return True
        if d.startswith("self.") and d.count(".") == 1 and cls is not None:
            return seg in self.class_locks.get((mod, cls), {})
        return seg in self._attr_owner

    # -- pass 2: function body scan -----------------------------------------

    def _scan_all(self) -> None:
        for info in self.funcs.values():
            self._scan(info)

    def _scan(self, info: FuncInfo) -> None:
        model = self

        class V(ast.NodeVisitor):
            def __init__(self):
                self.held: list = []  # (key|None, raw, line)

            def visit_With(self, node: ast.With):
                pushed = 0
                for item in node.items:
                    expr = item.context_expr
                    if model._is_lockish_expr(expr, info.module, info.cls):
                        raw = dotted(expr) or "<lock>"
                        key = model.lock_key(expr, info.module, info.cls)
                        info.acquires.append((key, raw, node.lineno))
                        if key is not None:
                            for hk, _, _ in self.held:
                                if hk is not None and hk != key:
                                    info.edges.append(
                                        (hk, key, info.path, node.lineno))
                        self.held.append((key, raw, node.lineno))
                        pushed += 1
                for stmt in node.body:
                    self.visit(stmt)
                for _ in range(pushed):
                    self.held.pop()

            visit_AsyncWith = visit_With

            def visit_Call(self, node: ast.Call):
                ref = model._call_ref(node, info)
                if ref is not None:
                    info.calls_all.append(ref)
                    if self.held:
                        heldkeys = tuple(hk for hk, _, _ in self.held
                                         if hk is not None)
                        info.calls_under.append(
                            (ref, heldkeys, self.held[-1][1], node.lineno))
                if self.held:
                    name = dotted(node.func) or terminal(node.func) or "?"
                    if _is_blocking_call(node):
                        info.blocking.append(
                            (name, self.held[-1][1], node.lineno))
                t = terminal(node.func)
                d = dotted(node.func) or ""
                if t == "Thread" and (d == "threading.Thread" or d == "Thread"):
                    info.thread_spawns.append(node)
                self.generic_visit(node)

            # a nested def's body is NOT executed under the enclosing
            # lock; it is scanned as its own FuncInfo.
            def visit_FunctionDef(self, node):
                pass

            visit_AsyncFunctionDef = visit_FunctionDef
            visit_Lambda = visit_FunctionDef

            def visit_ClassDef(self, node):
                pass

        v = V()
        for stmt in info.node.body:
            v.visit(stmt)

    def _call_ref(self, node: ast.Call, info: FuncInfo):
        d = dotted(node.func)
        if d is None:
            return None
        parts = d.split(".")
        if len(parts) == 1:
            return ("bare", parts[0])
        if parts[0] == "self" and len(parts) == 2:
            return ("self", parts[1])
        if len(parts) == 2 and parts[0] in self.imports.get(info.module, {}):
            return ("mod", parts[0], parts[1])
        return ("attr", parts[-1])

    def resolve_ref(self, ref, info: FuncInfo) -> str | None:
        kind = ref[0]
        if kind == "self" and info.cls is not None:
            return self.methods.get((info.module, info.cls), {}).get(ref[1])
        if kind == "bare":
            fk = self.module_funcs.get(info.module, {}).get(ref[1])
            if fk:
                return fk
            tgt = self.from_funcs.get(info.module, {}).get(ref[1])
            if tgt:
                return self.module_funcs.get(tgt[0], {}).get(tgt[1])
            return None
        if kind == "mod":
            tgt = self.imports.get(info.module, {}).get(ref[1])
            if tgt is not None:
                return self.module_funcs.get(tgt, {}).get(ref[2])
            return None
        if kind == "attr":
            owners = self._method_owner.get(ref[1])
            if owners and len(owners) == 1:
                return next(iter(owners))
        return None

    # -- transitive may-acquire sets ----------------------------------------

    def _closure(self) -> dict:
        may: dict = {k: {a for a, _, _ in f.acquires if a is not None}
                     for k, f in self.funcs.items()}
        changed = True
        guard = 0
        while changed and guard < 64:
            changed = False
            guard += 1
            for key, f in self.funcs.items():
                cur = may[key]
                before = len(cur)
                for ref in f.calls_all:
                    callee = self.resolve_ref(ref, f)
                    if callee is not None and callee != key:
                        cur |= may.get(callee, set())
                if len(cur) != before:
                    changed = True
        return may


def _model(project: Project) -> LockModel:
    m = getattr(project, "_tmlint_lock_model", None)
    if m is None:
        m = LockModel(project)
        project._tmlint_lock_model = m
    return m


# ---------------------------------------------------------------------------
# Rule: lock-held-call
# ---------------------------------------------------------------------------

# Blocking or callback-invoking terminals that must never run under a held
# lock. `wait`/`notify` are excluded: Condition.wait under its own lock is
# the correct idiom. Thread.join is matched only on thread-shaped targets
# (str.join is everywhere).
_BLOCKING_TERMINALS = {
    "sleep", "sendall", "recv", "recv_into", "accept", "connect",
    "create_connection", "getaddrinfo", "device_get", "block_until_ready",
    "send", "try_send", "broadcast", "dial", "dial_peer",
    "stop_peer_for_error", "stop_peer_by_id",
}
_CALLBACK_BARE_NAMES = {"cb", "callback", "fn", "handler", "listener", "hook"}


def _is_blocking_call(node: ast.Call) -> bool:
    t = terminal(node.func)
    if t is None:
        return False
    if t in _BLOCKING_TERMINALS:
        return True
    if t.startswith("on_"):
        return True
    if isinstance(node.func, ast.Name) and t in _CALLBACK_BARE_NAMES:
        return True
    if t == "join" and isinstance(node.func, ast.Attribute):
        v = dotted(node.func.value) or ""
        if "thread" in v.lower():
            return True
    return False


@rule("lock-held-call",
      "no blocking or callback-invoking calls while holding a lock")
def check_lock_held_call(project: Project) -> list[Finding]:
    model = _model(project)
    out = []
    for info in model.funcs.values():
        for name, lockraw, line in info.blocking:
            out.append(Finding(
                info.path, line, "lock-held-call",
                f"call to {name}() inside `with {lockraw}:` — blocking/"
                f"callback work must move outside the lock"))
    return out


# ---------------------------------------------------------------------------
# Rule: lock-order
# ---------------------------------------------------------------------------


@rule("lock-order",
      "the cross-module static lock-acquisition graph must be acyclic")
def check_lock_order(project: Project) -> list[Finding]:
    model = _model(project)
    edges: dict = {}   # (A, B) -> (path, line, note)
    selfdead: list = []
    for info in model.funcs.values():
        for a, b, path, line in info.edges:
            edges.setdefault((a, b), (path, line, "nested with"))
        for ref, held, _, line in info.calls_under:
            if not held:
                continue
            callee = model.resolve_ref(ref, info)
            if callee is None:
                continue
            for lk in sorted(model.may_acquire.get(callee, ())):
                for hk in held:
                    if hk == lk:
                        # same key via a self-call chain on a non-reentrant
                        # lock: guaranteed self-deadlock
                        if (ref[0] == "self"
                                and model.lock_kind(lk) == "Lock"
                                and lk in {a for a, _, _ in
                                           model.funcs[callee].acquires}):
                            selfdead.append((info.path, line, lk, callee))
                        continue
                    edges.setdefault(
                        (hk, lk),
                        (info.path, line, f"via {callee.split(':')[-1]}()"))
    out = []
    for path, line, lk, callee in selfdead:
        out.append(Finding(
            path, line, "lock-order",
            f"non-reentrant lock {lk} re-acquired via self-call "
            f"{callee.split(':')[-1]}() while already held "
            f"(guaranteed deadlock)"))
    # Tarjan SCC over the edge set
    graph: dict = {}
    for (a, b) in edges:
        graph.setdefault(a, set()).add(b)
        graph.setdefault(b, set())
    sccs = _tarjan(graph)
    for scc in sccs:
        if len(scc) < 2:
            continue
        cyc = sorted(scc)
        cyc_edges = sorted((a, b) for (a, b) in edges
                           if a in scc and b in scc)
        # no line numbers in the MESSAGE: it is the baseline identity and
        # must survive unrelated line drift (the finding's own line field
        # carries the location)
        detail = "; ".join(
            f"{a}->{b} in {edges[(a, b)][0]} ({edges[(a, b)][2]})"
            for a, b in cyc_edges)
        path, line, _ = edges[cyc_edges[0]]
        out.append(Finding(
            path, line, "lock-order",
            f"lock-order cycle among {{{', '.join(cyc)}}}: {detail}"))
    return out


def _tarjan(graph: dict) -> list[set]:
    index: dict = {}
    low: dict = {}
    on_stack: set = set()
    stack: list = []
    sccs: list = []
    counter = [0]

    def strongconnect(v):
        work = [(v, iter(sorted(graph.get(v, ()))))]
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on_stack.add(v)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on_stack.add(w)
                    work.append((w, iter(sorted(graph.get(w, ())))))
                    advanced = True
                    break
                elif w in on_stack:
                    low[node] = min(low[node], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                scc = set()
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    scc.add(w)
                    if w == node:
                        break
                sccs.append(scc)

    for v in sorted(graph):
        if v not in index:
            strongconnect(v)
    return sccs


# ---------------------------------------------------------------------------
# Rule: device-sync-choke-point
# ---------------------------------------------------------------------------

# Where host<->device syncs are ALLOWED: the kernel modules (finishers,
# probes, warmup) and the two audited choke FUNCTIONS —
# crypto/batch._device_get (every PendingVerify/prefetch readback) and
# crypto/verify_service._readback (the continuous-batching service's
# single blocking fetch, itself routed through _device_get). Everything
# else must go through PendingVerify/resolve_all or the service; a stray
# device_get/block_until_ready anywhere else re-introduces an unshared
# host<->device round trip.
_DEVICE_ALLOW_DIRS = ("tendermint_tpu/ops/",)
_DEVICE_CHOKE_FUNCS = (
    ("tendermint_tpu/crypto/batch.py", "_device_get"),
    ("tendermint_tpu/crypto/verify_service.py", "_readback"),
)


@rule("device-sync-choke-point",
      "jax.device_get/block_until_ready/np.asarray only at audited sites")
def check_device_sync(project: Project) -> list[Finding]:
    out = []
    choke_by_file: dict = {}
    for path, func in _DEVICE_CHOKE_FUNCS:
        choke_by_file.setdefault(path, set()).add(func)
    for sf in project.prod_files():
        if sf.path.startswith(_DEVICE_ALLOW_DIRS):
            continue
        choke_ranges = []
        for func in choke_by_file.get(sf.path, ()):
            for node in ast.walk(sf.tree):
                if (isinstance(node, ast.FunctionDef)
                        and node.name == func):
                    choke_ranges.append(
                        (node.lineno, max(getattr(n, "end_lineno", node.lineno)
                                          for n in ast.walk(node))))
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            t = terminal(node.func)
            d = dotted(node.func) or ""
            hit = None
            if t == "device_get":
                hit = d or "device_get"
            elif t == "block_until_ready":
                hit = f"{d}()" if d else "block_until_ready"
            elif d in ("np.asarray", "numpy.asarray"):
                hit = d
            if hit is None:
                continue
            if any(lo <= node.lineno <= hi for lo, hi in choke_ranges):
                continue
            out.append(Finding(
                sf.path, node.lineno, "device-sync-choke-point",
                f"{hit} outside the audited sync sites — route through "
                f"crypto/batch._device_get (PendingVerify/resolve_all) or "
                f"the verify service's _readback so blocking round trips "
                f"stay at the audited choke points"))
    return out


# ---------------------------------------------------------------------------
# Rules: thread-crash-surface, daemon-or-joined
# ---------------------------------------------------------------------------


def _broad_try(stmt) -> bool:
    if not isinstance(stmt, ast.Try):
        return False
    for h in stmt.handlers:
        if h.type is None:
            return True
        names = []
        if isinstance(h.type, ast.Tuple):
            names = [terminal(e) for e in h.type.elts]
        else:
            names = [terminal(h.type)]
        if any(n in ("Exception", "BaseException") for n in names):
            return True
    return False


def _body_after_docstring(fd):
    body = list(fd.body)
    if (body and isinstance(body[0], ast.Expr)
            and isinstance(body[0].value, ast.Constant)
            and isinstance(body[0].value.value, str)):
        body = body[1:]
    return body


def _is_crash_shielded(model: LockModel, fd, depth: int = 0) -> bool:
    """A thread target survives anything if a broad try/except wraps its
    work: a top-level Try, a Try at the top of a top-level loop, or full
    delegation to a function that is itself shielded."""
    if fd is None or depth > 3:
        return False
    body = _body_after_docstring(fd.node if isinstance(fd, FuncInfo) else fd)
    node = fd.node if isinstance(fd, FuncInfo) else fd
    for stmt in body:
        if _broad_try(stmt):
            return True
        # ...or at the top of a top-level loop / with region (shield inside
        # the drain loop, or under a build lock) — same guarantee
        if isinstance(stmt, (ast.While, ast.For, ast.With)):
            if any(_broad_try(s) for s in stmt.body):
                return True
    # delegation: def run(): self._real_run()
    if len(body) == 1:
        inner = body[0]
        call = None
        if isinstance(inner, ast.Expr) and isinstance(inner.value, ast.Call):
            call = inner.value
        elif isinstance(inner, ast.Return) and isinstance(inner.value, ast.Call):
            call = inner.value
        if call is not None and isinstance(fd, FuncInfo):
            ref = model._call_ref(call, fd)
            if ref is not None:
                callee = model.resolve_ref(ref, fd)
                if callee is not None:
                    return _is_crash_shielded(model, model.funcs[callee],
                                              depth + 1)
    return False


def _resolve_thread_target(model: LockModel, info: FuncInfo, expr):
    """Map a Thread(target=...) expression to a FuncInfo, or None when the
    target is library code (e.g. httpd.serve_forever) we cannot see."""
    if isinstance(expr, ast.Lambda):
        if isinstance(expr.body, ast.Call):
            return _resolve_thread_target(model, info, expr.body.func)
        return None
    if isinstance(expr, ast.Call):  # functools.partial(f, ...)
        if terminal(expr.func) == "partial" and expr.args:
            return _resolve_thread_target(model, info, expr.args[0])
        return None
    d = dotted(expr)
    if d is None:
        return None
    parts = d.split(".")
    if len(parts) == 1:
        # nested def in the same function, then module-level
        nested = model.funcs.get(f"{info.key}.{parts[0]}")
        if nested is not None:
            return nested
        fk = model.module_funcs.get(info.module, {}).get(parts[0])
        return model.funcs.get(fk) if fk else None
    if parts[0] == "self" and len(parts) == 2 and info.cls is not None:
        fk = model.methods.get((info.module, info.cls), {}).get(parts[1])
        return model.funcs.get(fk) if fk else None
    return None


@rule("thread-crash-surface",
      "every in-tree Thread target needs a top-level broad try/except")
def check_thread_crash_surface(project: Project) -> list[Finding]:
    model = _model(project)
    out = []
    for info in model.funcs.values():
        for call in info.thread_spawns:
            tgt = _kwarg(call, "target")
            if tgt is None:
                continue
            target = _resolve_thread_target(model, info, tgt)
            if target is None:
                continue  # library target; nothing to inspect
            if not _is_crash_shielded(model, target):
                out.append(Finding(
                    info.path, call.lineno, "thread-crash-surface",
                    f"Thread target {target.key.split(':')[-1]}() has no "
                    f"top-level try/except Exception — a stray exception "
                    f"kills the routine silently"))
    return out


@rule("daemon-or-joined",
      "every Thread is daemonized or tracked for join")
def check_daemon_or_joined(project: Project) -> list[Finding]:
    model = _model(project)
    # joined attr/name terminals per module, e.g. self._thread.join()
    joined: dict = {}
    for sf in project.prod_files():
        mod = _short_module(sf.path)
        names = joined.setdefault(mod, set())
        for node in ast.walk(sf.tree):
            if (isinstance(node, ast.Call)
                    and terminal(node.func) == "join"
                    and isinstance(node.func, ast.Attribute)):
                base = terminal(node.func.value)
                if base:
                    names.add(base)
    out = []
    for info in model.funcs.values():
        # daemon flags set in this function: `t.daemon = True`
        daemoned = set()
        for node in ast.walk(info.node):
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if (isinstance(tgt, ast.Attribute)
                            and tgt.attr == "daemon"):
                        base = terminal(tgt.value)
                        if base:
                            daemoned.add(base)
        # map call node -> assignment target terminal
        assigned: dict = {}
        for node in ast.walk(info.node):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                base = terminal(node.targets[0])
                if base:
                    assigned[id(node.value)] = base
        for call in info.thread_spawns:
            if _kwarg(call, "daemon") is not None:
                continue
            base = assigned.get(id(call))
            if base is not None:
                if base in daemoned:
                    continue
                if base in joined.get(info.module, set()):
                    continue
            out.append(Finding(
                info.path, call.lineno, "daemon-or-joined",
                "Thread is neither daemon=True nor joined anywhere in its "
                "module — it can outlive stop() and hang teardown"))
    return out


# ---------------------------------------------------------------------------
# Rule: metrics-discipline
# ---------------------------------------------------------------------------


@rule("metrics-discipline",
      "labeled counters/gauges pre-seeded or removal-disciplined")
def check_metrics_discipline(project: Project) -> list[Finding]:
    out = []
    # Seeds/removals are collected project-wide: a metric created in
    # utils/metrics.py may be removal-disciplined by the node sampler
    # (Gauge.remove on peer departure) in node/node.py.
    seeded: set = set()
    removed: set = set()
    for sf in project.prod_files():
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            metric = terminal(node.func.value)
            if metric is None:
                continue
            if node.func.attr in ("add", "set") and node.args:
                a0 = node.args[0]
                if isinstance(a0, ast.Constant) and a0.value in (0, 0.0):
                    seeded.add(metric)
            elif node.func.attr == "remove":
                removed.add(metric)
    for sf in project.prod_files():
        # creations: self.NAME = r.counter/gauge(..., labels=(...))
        created = []  # (attrname, kind, line)
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                continue
            t = terminal(node.value.func)
            if t not in ("counter", "gauge"):
                continue
            labels = _kwarg(node.value, "labels")
            if labels is None and len(node.value.args) >= 4:
                labels = node.value.args[3]
            if labels is None:
                continue
            if (isinstance(labels, (ast.Tuple, ast.List))
                    and not labels.elts):
                continue
            tgt = dotted(node.targets[0]) if node.targets else None
            if not tgt:
                continue
            created.append((tgt.split(".")[-1], t, node.value.lineno))
        for name, kind, line in created:
            if name in seeded or name in removed:
                continue
            out.append(Finding(
                sf.path, line, "metrics-discipline",
                f"labeled {kind} '{name}' is never pre-seeded (add/set 0) "
                f"nor removal-disciplined — absent series break dashboards, "
                f"unbounded label values leak exposition lines"))
    return out


# ---------------------------------------------------------------------------
# Rule: fault-site-registry
# ---------------------------------------------------------------------------

_FAULTS_FILE = "tendermint_tpu/utils/faults.py"
_FAULTS_DOC = "docs/FAULTS.md"
_FIRE_FAMILY = {"fire", "maybe_drop", "link_outcome", "torn_write",
                "crash_point", "fail_point", "check", "mutate_value"}
_SITE_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)+$")


def _canonical_sites(project: Project) -> dict[str, int]:
    """site -> declaration line, parsed from the CANONICAL_SITES dict
    literal (no project import: the linter stays jax-free)."""
    sf = project.file(_FAULTS_FILE)
    sites: dict[str, int] = {}
    if sf is None or sf.tree is None:
        text = project.read_side_file(_FAULTS_FILE)
        if text is None:
            return sites
        try:
            sf_tree = ast.parse(text)
        except SyntaxError:
            # unparsable faults.py: degrade to the rule's own
            # "not found/parsable" finding (plus parse-error) instead of
            # aborting the whole lint run with a traceback
            return sites
    else:
        sf_tree = sf.tree
    for node in ast.walk(sf_tree):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        if (targets
                and any(isinstance(t, ast.Name) and t.id == "CANONICAL_SITES"
                        for t in targets)
                and isinstance(node.value, ast.Dict)):
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    sites[k.value] = k.lineno
    return sites


@rule("fault-site-registry",
      "faults.fire(...) site literals must be canonical and documented")
def check_fault_sites(project: Project) -> list[Finding]:
    sites = _canonical_sites(project)
    out = []
    if not sites:
        return [Finding(_FAULTS_FILE, 1, "fault-site-registry",
                        "CANONICAL_SITES dict not found/parsable")]
    namespaces = {s.split(".")[0] for s in sites}
    for sf in project.prod_files():
        if sf.path == _FAULTS_FILE:
            continue
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and terminal(node.func) in _FIRE_FAMILY
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            lit = node.args[0].value
            if not _SITE_RE.match(lit):
                continue
            if lit not in sites:
                out.append(Finding(
                    sf.path, node.lineno, "fault-site-registry",
                    f"fault site '{lit}' is not declared in "
                    f"utils/faults.py CANONICAL_SITES"))
    # docs cross-check
    doc = project.read_side_file(_FAULTS_DOC)
    if doc is None:
        out.append(Finding(_FAULTS_DOC, 1, "fault-site-registry",
                           "docs/FAULTS.md missing"))
        return out
    for site in sorted(sites):
        # abbreviated table rows (`a.b.{x} … y / z`) count via last segment
        if site not in doc and site.split(".")[-1] not in doc:
            out.append(Finding(
                _FAULTS_FILE, sites[site], "fault-site-registry",
                f"canonical site '{site}' is not documented in "
                f"docs/FAULTS.md"))
    for i, line in enumerate(doc.splitlines(), start=1):
        for tok in re.findall(r"`([^`]+)`", line):
            if (_SITE_RE.match(tok) and tok not in sites
                    and tok.split(".")[0] in namespaces):
                out.append(Finding(
                    _FAULTS_DOC, i, "fault-site-registry",
                    f"docs/FAULTS.md names site '{tok}' which is not in "
                    f"CANONICAL_SITES (stale or undeclared)"))
    return out


# ---------------------------------------------------------------------------
# Rule: trace-span-discipline
# ---------------------------------------------------------------------------

_TRACE_FILE = "tendermint_tpu/utils/trace.py"
_TRACE_DOC = "docs/OBSERVABILITY.md"
# The flight-recorder recording surface (utils/trace.py): dotted-name
# string literals passed to these terminals are span names. Non-dotted
# first args (peerscore offences, dict keys) never match _SITE_RE, so the
# family can stay broad without false positives.
_SPAN_FAMILY = {"span", "mark"}
_SPAN_RECORD = "record"


def _canonical_spans(project: Project) -> dict[str, int]:
    """span name -> declaration line, parsed from the CANONICAL_SPANS dict
    literal (no project import: the linter stays jax-free) — the exact
    pattern of fault-site-registry's CANONICAL_SITES."""
    sf = project.file(_TRACE_FILE)
    spans: dict[str, int] = {}
    if sf is None or sf.tree is None:
        text = project.read_side_file(_TRACE_FILE)
        if text is None:
            return spans
        try:
            tree = ast.parse(text)
        except SyntaxError:
            return spans
    else:
        tree = sf.tree
    for node in ast.walk(tree):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        if (targets
                and any(isinstance(t, ast.Name) and t.id == "CANONICAL_SPANS"
                        for t in targets)
                and isinstance(node.value, ast.Dict)):
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    spans[k.value] = k.lineno
    return spans


@rule("trace-span-discipline",
      "trace.span/mark/record name literals must be canonical + documented")
def check_trace_spans(project: Project) -> list[Finding]:
    spans = _canonical_spans(project)
    out = []
    if not spans:
        return [Finding(_TRACE_FILE, 1, "trace-span-discipline",
                        "CANONICAL_SPANS dict not found/parsable")]
    namespaces = {s.split(".")[0] for s in spans}
    for sf in project.prod_files():
        if sf.path == _TRACE_FILE:
            continue
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            t = terminal(node.func)
            if t not in _SPAN_FAMILY and t != _SPAN_RECORD:
                continue
            lit = node.args[0].value
            if not _SITE_RE.match(lit):
                continue
            if t == _SPAN_RECORD and not isinstance(node.func, ast.Attribute):
                continue  # a bare record() is some other module's function
            if lit not in spans:
                out.append(Finding(
                    sf.path, node.lineno, "trace-span-discipline",
                    f"trace span '{lit}' is not declared in "
                    f"utils/trace.py CANONICAL_SPANS — ad-hoc span names "
                    f"drift from docs/OBSERVABILITY.md"))
    doc = project.read_side_file(_TRACE_DOC)
    if doc is None:
        out.append(Finding(_TRACE_DOC, 1, "trace-span-discipline",
                           "docs/OBSERVABILITY.md missing"))
        return out
    for span_name in sorted(spans):
        if span_name not in doc:
            out.append(Finding(
                _TRACE_FILE, spans[span_name], "trace-span-discipline",
                f"canonical span '{span_name}' is not documented in "
                f"docs/OBSERVABILITY.md"))
    for i, line in enumerate(doc.splitlines(), start=1):
        for tok in re.findall(r"`([^`]+)`", line):
            if (_SITE_RE.match(tok) and tok not in spans
                    and tok.split(".")[0] in namespaces
                    and "." in tok):
                out.append(Finding(
                    _TRACE_DOC, i, "trace-span-discipline",
                    f"docs/OBSERVABILITY.md names span '{tok}' which is "
                    f"not in CANONICAL_SPANS (stale or undeclared)"))
    return out


# ---------------------------------------------------------------------------
# Rule: config-knob-parity
# ---------------------------------------------------------------------------

_CONFIG_DOC = "docs/CONFIG.md"
_KNOB_RE = re.compile(r"\bTM_TPU_[A-Z0-9][A-Z0-9_]*\b|\bTMTPU_[A-Z0-9][A-Z0-9_]*\b")


def _knob_tokens_in_tree(tree) -> dict[str, int]:
    toks: dict[str, int] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            for tok in _KNOB_RE.findall(node.value):
                toks.setdefault(tok, node.lineno)
    return toks


def _scan_covers_default_scope(project: Project) -> bool:
    """True when every DEFAULT_PATHS entry that exists on disk is in the
    scanned set. The doc->code ("stale doc") direction is only sound
    then: a subset scan (`tmlint tendermint_tpu tests`) simply cannot see
    a knob read only in bench.py and must not call its doc entry stale."""
    from tools.tmlint.core import DEFAULT_PATHS

    for p in DEFAULT_PATHS:
        if not os.path.exists(os.path.join(project.root, p)):
            continue
        covered = any(sf.path == p or sf.path.startswith(p + "/")
                      for sf in project.files)
        if not covered:
            return False
    return True


@rule("config-knob-parity",
      "every TM_TPU_*/TMTPU_* env knob in code <-> docs/CONFIG.md")
def check_knob_parity(project: Project) -> list[Finding]:
    code: dict[str, tuple[str, int]] = {}
    for sf in project.files:
        if sf.tree is None or sf.path.startswith("tools/tmlint/"):
            continue
        for tok, line in sorted(_knob_tokens_in_tree(sf.tree).items()):
            code.setdefault(tok, (sf.path, line))
    doc = project.read_side_file(_CONFIG_DOC)
    if doc is None:
        return [Finding(_CONFIG_DOC, 1, "config-knob-parity",
                        "docs/CONFIG.md missing")]
    doc_toks: dict[str, int] = {}
    for i, line in enumerate(doc.splitlines(), start=1):
        for tok in _KNOB_RE.findall(line):
            doc_toks.setdefault(tok, i)
    out = []
    for tok in sorted(set(code) - set(doc_toks)):
        path, line = code[tok]
        out.append(Finding(
            path, line, "config-knob-parity",
            f"env knob {tok} is used in code but undocumented in "
            f"docs/CONFIG.md"))
    if _scan_covers_default_scope(project):
        for tok in sorted(set(doc_toks) - set(code)):
            out.append(Finding(
                _CONFIG_DOC, doc_toks[tok], "config-knob-parity",
                f"docs/CONFIG.md documents {tok} but nothing in the tree "
                f"reads it (stale doc)"))
    return out
