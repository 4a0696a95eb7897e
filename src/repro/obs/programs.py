"""Compiled programs and the model layers their instructions belong to.

A TPU profile names each device op by its HLO instruction (``fusion.958``,
``copy.1832``) and carries no framework op path.  The compiled program's
optimized HLO holds the same instruction names, and each instruction's
``metadata={op_name="jit(fn)/.../slstm/..."}`` holds every
``jax.named_scope`` it was traced under.  ``PROGRAMS`` joins the two: a
runtime registers its train step (the step function, its jit arguments and
abstract inputs: references only, nothing is lowered or compiled), and
``PROGRAMS.scopes(app_id)``, asked for only when a profile is read, compiles
that step once more and returns ``{instruction name: scope}``.

Attribution rules, for the scopes of ``repro.models.LAYER_SCOPES``:

* an instruction's scope is the innermost of those scopes on its
  ``op_name`` path; transform wrappers (``jvp(...)``, ``transpose(...)``,
  ``checkpoint``/``rematted_computation``) are looked through, so the
  backward pass and the rematerialised forward land in the layer that
  caused them;
* an instruction whose ``op_name`` names none of them (or that has no
  ``op_name``, as the copies layout assignment inserts) takes the scope of
  the instruction whose computation holds it (a ``while`` body, a
  conditional branch, a call), recursively: a relayout copy in the sLSTM
  scan's loop body is sLSTM time;
* anything else is ``UNSCOPED``.

The compile for the map is made with
``jax_compilation_cache_include_metadata_in_key`` on, through a jit of its
own.  By default JAX strips metadata from the persistent-cache key, so an
executable cached by a build of the code without these scopes would come
back with that build's (stale) metadata; a fresh jit wrapper also keeps
JAX's in-memory caches from handing back the runtime's own executable.
The optimized HLO is the same instruction for instruction, so its names are
those the profile shows.

Importing this module does not import JAX.
"""
from __future__ import annotations

import dataclasses
import functools
import re
import threading
from typing import Any, Callable, Dict, Optional, Tuple

from repro.models import LAYER_SCOPES

#: the scope of an instruction that no layer scope covers
UNSCOPED = "unscoped"

_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(map(re.escape, LAYER_SCOPES))
                    + r")(?=$|[/)])")
_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation|"
                    r"false_computation)=%([^\s,}]+)")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)="
                         r"\{([^}]*)\}")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost layer scope on an ``op_name`` path, or None."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


@dataclasses.dataclass(frozen=True)
class ScopeMap:
    """One compiled program's instructions and the layer each belongs to."""
    module: str                    # the HLO module's name, e.g. ``jit_fn``
    scope: Dict[str, str]          # instruction name -> scope or UNSCOPED


def parse_hlo(text: str) -> ScopeMap:
    """Read an optimized HLO module's text (``Compiled.as_text()``) into a
    ``ScopeMap`` by the rules in the module docstring."""
    module = ""
    own: Dict[str, Optional[str]] = {}
    home: Dict[str, str] = {}          # instruction -> its computation
    holder: Dict[str, str] = {}        # computation -> first calling inst
    comp = None
    for line in text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        if line.endswith("{") and not line[:1].isspace():
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        own[name] = scope_of(op.group(1)) if op else None
        home[name] = comp
        callees = _CALLS.findall(rest)
        for group in _CALL_LISTS.findall(rest):
            callees += [c.strip().lstrip("%") for c in group.split(",")
                        if c.strip()]
        for callee in callees:
            holder.setdefault(callee, name)

    resolved: Dict[str, str] = {}

    def resolve(name: str) -> str:
        chain = []
        while name not in resolved:
            if own[name] is not None:
                resolved[name] = own[name]
                break
            chain.append(name)
            up = holder.get(home[name])
            if up is None or up in chain:
                resolved[name] = UNSCOPED
                break
            name = up
        for n in chain:
            resolved[n] = resolved[name]
        return resolved[name]

    return ScopeMap(module, {n: resolve(n) for n in own})


def compile_fresh(fn: Callable, args: Tuple, jit_kwargs: Dict[str, Any]):
    """Compile ``fn`` for ``args`` through a jit wrapper of its own, with
    metadata in the persistent-cache key (see the module docstring)."""
    import jax
    from jax._src import config as jax_config

    @functools.wraps(fn)
    def fresh(*a):
        return fn(*a)

    with jax_config.compilation_cache_include_metadata_in_key(True):
        return jax.jit(fresh, **jit_kwargs).lower(*args).compile()


class Programs:
    """Registered step programs by app id, and their scope maps on demand."""

    def __init__(self):
        self._lock = threading.Lock()
        self._steps: Dict[str, Tuple[Callable, Tuple, Dict[str, Any]]] = {}
        self._maps: Dict[str, ScopeMap] = {}

    def register(self, app_id: str, fn: Callable, args: Tuple,
                 **jit_kwargs) -> None:
        """Note ``jax.jit(fn, **jit_kwargs)`` over the abstract ``args`` as
        ``app_id``'s step.  Stores the references only."""
        with self._lock:
            self._steps[app_id] = (fn, tuple(args), dict(jit_kwargs))
            self._maps.pop(app_id, None)

    def forget(self, app_id: str) -> None:
        with self._lock:
            self._steps.pop(app_id, None)
            self._maps.pop(app_id, None)

    def scopes(self, app_id: str) -> Optional[ScopeMap]:
        """``app_id``'s step as a ``ScopeMap``; compiles it the first time
        it is asked for.  None if no step is registered under the id."""
        with self._lock:
            step = self._steps.get(app_id)
            cached = self._maps.get(app_id)
        if step is None or cached is not None:
            return cached
        smap = parse_hlo(compile_fresh(*step).as_text())
        with self._lock:
            if self._steps.get(app_id) is step:
                self._maps[app_id] = smap
        return smap


#: the process-global registry the runtime registers its train steps in
PROGRAMS = Programs()
