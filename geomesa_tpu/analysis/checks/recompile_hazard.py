"""Check ``recompile-hazard``: ``jax.jit`` / ``pjit`` / ``shard_map``
/ ``pallas_call`` sites free of the silent-retrace traps.

The warm-recompile budget (``test_zz_obs``) only catches retraces the
suite happens to EXECUTE; this check reads the hazard off the source:

* **unhashable static defaults** — a jit-wrapped def whose static
  argument has a list/dict/set default raises (or retraces) the first
  time the default is used;
* **unhashable static call values** — passing a list/dict/set display
  for a declared static argument at a call site;
* **per-call-varying static values** — a static argument computed
  from ``time``/``random``/``uuid``/``id(...)`` retraces on every
  call: the classic "every query compiles" TPU cliff;
* **mutable-global capture** — a jitted function reading a module
  global that is (a) bound to a mutable literal AND mutated somewhere
  in the module, or (b) reassigned via ``global``: tracing bakes the
  value in at first call, so later mutation silently diverges (or, if
  it changes hashability/shape, retraces).

Static-argument names resolve through the walker's jit registry
(``static_argnames`` literals; ``static_argnums`` mapped through the
wrapped def's positional parameters), including imported jitted
functions.
"""

from __future__ import annotations

import ast
import builtins

from ..walker import _dotted, jit_call_info

__all__ = ["RecompileHazardCheck"]

_UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.DictComp, ast.SetComp,
               ast.ListComp)

#: call roots whose results differ per call — a static arg computed
#: from one retraces every dispatch
_VARYING_ROOTS = ("time.", "random.", "uuid.", "datetime.", "os.urandom",
                  "id", "perf_counter", "monotonic")

_BUILTINS = frozenset(dir(builtins))


def _is_varying_call(node) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            dotted = _dotted(sub.func)
            if any(dotted == r.rstrip(".") or dotted.startswith(r)
                   for r in _VARYING_ROOTS):
                return True
    return False


def _local_names(fn) -> set[str]:
    """Parameters + every name the function binds (assignment,
    comprehension, with/for targets) — reads outside this set are
    global/closure reads."""
    out = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                           + fn.args.kwonlyargs)}
    if fn.args.vararg:
        out.add(fn.args.vararg.arg)
    if fn.args.kwarg:
        out.add(fn.args.kwarg.arg)
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) \
                and isinstance(node.ctx, (ast.Store, ast.Del)):
            out.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
            out.add(node.name)
        elif isinstance(node, ast.ImportFrom) or isinstance(node, ast.Import):
            for alias in node.names:
                out.add((alias.asname or alias.name).split(".")[0])
    return out


_MUTATORS = {"append", "extend", "insert", "update", "setdefault", "pop",
             "popitem", "clear", "add", "discard", "remove", "__setitem__"}


def _module_global_hazards(tree) -> dict[str, str]:
    """Module-level names that are mutation hazards: name -> why."""
    mutable_literal: set[str] = set()
    immutable: set[str] = set()
    for node in tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = [t for t in node.targets if isinstance(t, ast.Name)]
            value = node.value
        elif isinstance(node, ast.AnnAssign) \
                and isinstance(node.target, ast.Name):
            targets, value = [node.target], node.value
        else:
            continue
        for t in targets:
            if isinstance(value, _UNHASHABLE) or (
                    isinstance(value, ast.Call)
                    and _dotted(value.func) in ("list", "dict", "set")):
                mutable_literal.add(t.id)
            else:
                immutable.add(t.id)
    hazards: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Global):
            for name in node.names:
                hazards[name] = "reassigned via `global`"
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in _MUTATORS \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id in mutable_literal:
            hazards.setdefault(node.func.value.id,
                               "a mutable literal mutated in-module")
        elif isinstance(node, (ast.Subscript, ast.AugAssign)):
            target = node.target if isinstance(node, ast.AugAssign) \
                else node.value
            if isinstance(target, ast.Name) \
                    and target.id in mutable_literal \
                    and (isinstance(node, ast.AugAssign)
                         or isinstance(node.ctx, (ast.Store, ast.Del))):
                hazards.setdefault(target.id,
                                   "a mutable literal mutated in-module")
    return hazards


class RecompileHazardCheck:
    id = "recompile-hazard"
    description = ("jit/pjit/shard_map/pallas_call sites: unhashable or "
                   "per-call-varying static args, jitted closures over "
                   "mutable module globals")

    def run(self, mod, project):
        hazards = _module_global_hazards(mod.tree)
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_def(mod, node, hazards)
            elif isinstance(node, ast.Call):
                yield from self._check_call(mod, node, project)

    def _check_def(self, mod, fn, hazards):
        statics = mod.jitted_fns.get(fn.name)
        if statics is None:
            return
        # unhashable defaults on static params
        pos = fn.args.posonlyargs + fn.args.args
        defaults = fn.args.defaults
        for arg, default in zip(pos[len(pos) - len(defaults):], defaults):
            if arg.arg in statics and isinstance(default, _UNHASHABLE):
                yield mod.finding(
                    self.id, default,
                    f"static argument `{arg.arg}` of jitted "
                    f"`{fn.name}` has an unhashable default — jit "
                    f"static args must be hashable")
        for arg, default in zip(fn.args.kwonlyargs, fn.args.kw_defaults):
            if default is not None and arg.arg in statics \
                    and isinstance(default, _UNHASHABLE):
                yield mod.finding(
                    self.id, default,
                    f"static argument `{arg.arg}` of jitted "
                    f"`{fn.name}` has an unhashable default — jit "
                    f"static args must be hashable")
        # mutable-global capture by the traced body
        local = _local_names(fn)
        reported: set[str] = set()
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load) \
                    and sub.id in hazards and sub.id not in local \
                    and sub.id not in _BUILTINS \
                    and sub.id not in reported:
                reported.add(sub.id)
                yield mod.finding(
                    self.id, sub,
                    f"jitted `{fn.name}` closes over module global "
                    f"`{sub.id}` ({hazards[sub.id]}) — tracing bakes "
                    f"the value in; later mutation silently diverges "
                    f"or retraces")

    def _check_call(self, mod, call, project):
        if not isinstance(call.func, ast.Name):
            return
        statics = project.static_args_of(mod, call.func.id)
        if not statics:
            return
        params = project.params_of(mod, call.func.id)
        # keyword AND positional values landing on static parameters
        sites = [(kw.arg, kw.value) for kw in call.keywords]
        for i, a in enumerate(call.args):
            if isinstance(a, ast.Starred):
                break  # positions past a *splat are unknowable
            if i < len(params):
                sites.append((params[i], a))
        for name, value in sites:
            if name not in statics:
                continue
            if isinstance(value, _UNHASHABLE):
                yield mod.finding(
                    self.id, value,
                    f"unhashable value for static argument "
                    f"`{name}` of jitted `{call.func.id}` — pass a "
                    f"tuple/scalar")
            elif _is_varying_call(value):
                yield mod.finding(
                    self.id, value,
                    f"static argument `{name}` of jitted "
                    f"`{call.func.id}` varies per call "
                    f"(time/random/id source) — every call retraces; "
                    f"bucket or hoist the value")
