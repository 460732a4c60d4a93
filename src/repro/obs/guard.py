"""Recompile sentinel: count jit compilations across a region, loudly.

``jax`` recompiles silently whenever a traced function sees a new static
signature — new array shapes/dtypes, a new pytree structure, a changed
static argument.  For this repro that is a correctness bug, not a perf
wobble: the whole scenario catalog must run under ONE compiled step (pure
array swaps), and a stray recompile on the training path can cost minutes.

:func:`compile_guard` turns that invariant into a runtime guard::

    step = jax.jit(wenv.step)
    step(key, state, action, params0)            # warm-up: compiles once
    with compile_guard("scenario catalog"):      # region must not compile
        for p in all_params[1:]:
            step(key, state, action, p)

On violation it raises :class:`RecompileError` naming each offending
function together with the argument avals that triggered the new cache
entry — the information you need to find the leaked python scalar / changed
shape.  Detection listens to jax's own compilation log (``jax.log_compiles``)
so it sees *every* compile in the region, including nested jits the caller
never wrapped.

Used by ``tests/envs/test_protocol.py`` (the CI protocol-conformance job),
``benchmarks/speed_table.py`` (real-data params must reuse the synthetic
entry) and the ``rl_train`` scenario preflight.
"""
from __future__ import annotations

import contextlib
import logging
import re
from typing import Any, Iterator, NamedTuple

import jax

# the logger jax emits "Compiling jit(<name>) with global shapes and types
# (<avals>,). Argument mapping: ..." records on (at WARNING) while
# jax.log_compiles() is active
_COMPILE_LOGGER = "jax._src.interpreters.pxla"
_COMPILE_RE = re.compile(r"^Compiling (.+?) with global shapes and types (.*)\. Argument mapping")


class CompileEvent(NamedTuple):
    """One observed compilation: the jitted callable's name + its avals."""

    name: str
    avals: str
    message: str


class RecompileError(RuntimeError):
    """A guarded region compiled more than its allowance."""

    def __init__(self, label: str, events: list[CompileEvent], max_compiles: int):
        self.events = events
        lines = "\n".join(f"  - {e.name}: {e.avals}" for e in events)
        super().__init__(
            f"compile_guard({label!r}): {len(events)} compilation(s) in a "
            f"region allowing {max_compiles} — offending functions and "
            f"argument avals:\n{lines}\n"
            "Recompiles mean a static signature changed (new shape/dtype, "
            "new pytree structure, python-scalar leak). Scenario/params "
            "swaps must be pure array swaps."
        )


class _CaptureHandler(logging.Handler):
    def __init__(self, allow: tuple[str, ...]):
        super().__init__(level=logging.DEBUG)
        self.allow = allow
        self.events: list[CompileEvent] = []

    def emit(self, record: logging.LogRecord) -> None:  # noqa: D102
        msg = record.getMessage()
        m = _COMPILE_RE.match(msg)
        if not m:
            return
        name = m.group(1)
        if any(a in name for a in self.allow):
            return
        self.events.append(CompileEvent(name, m.group(2), msg))


class CompileGuard:
    """Handle yielded by :func:`compile_guard` — inspect ``.events`` /
    ``.count`` inside the region (e.g. to log rather than raise)."""

    def __init__(self, handler: _CaptureHandler):
        self._handler = handler

    @property
    def events(self) -> list[CompileEvent]:
        return list(self._handler.events)

    @property
    def count(self) -> int:
        return len(self._handler.events)


@contextlib.contextmanager
def compile_guard(
    label: str = "region",
    max_compiles: int = 0,
    allow: tuple[str, ...] = (),
    raise_on_violation: bool = True,
) -> Iterator[CompileGuard]:
    """Guard a region against jit recompilation.

    Args:
        label: human-readable region name for the error message.
        max_compiles: compilations the region is allowed (0 = the region
            must run entirely from cache; 1 = e.g. "first call compiles").
        allow: substrings of function names to ignore (e.g. tiny host
            utilities like ``convert_element_type`` during warm-up).
        raise_on_violation: raise :class:`RecompileError` on exit when the
            allowance is exceeded (set False to only collect ``.events``).
    """
    handler = _CaptureHandler(tuple(allow))
    logger = logging.getLogger(_COMPILE_LOGGER)
    # keep the sentinel's probe lines off stderr while the region runs (the
    # dispatch logger emits per-compile timing lines under log_compiles too)
    muted = [logger, logging.getLogger("jax._src.dispatch")]
    prev_propagate = [lg.propagate for lg in muted]
    logger.addHandler(handler)
    for lg in muted:
        lg.propagate = False
    try:
        with jax.log_compiles():
            yield CompileGuard(handler)
    finally:
        logger.removeHandler(handler)
        for lg, p in zip(muted, prev_propagate):
            lg.propagate = p
    if raise_on_violation and len(handler.events) > max_compiles:
        raise RecompileError(label, handler.events, max_compiles)


def cache_entries(fn: Any) -> int:
    """Number of compiled entries in a ``jax.jit`` function's cache (the
    per-function view; :func:`compile_guard` is the region-wide one)."""
    try:
        return int(fn._cache_size())
    except AttributeError as e:  # pragma: no cover - jax version drift
        raise TypeError(
            f"{fn!r} has no jit cache (pass the jax.jit-wrapped callable)"
        ) from e


def assert_one_compiled_step(
    env: Any,
    params_list: list[Any],
    num_envs: int = 2,
    key: jax.Array | None = None,
    label: str = "scenario catalog",
) -> int:
    """Prove a parameter catalog shares ONE compiled step for ``env``.

    Steps ``env`` (any ``repro.envs.Environment``) once per params pytree:
    the first call may compile, every later call must hit the cache.
    Raises :class:`RecompileError` otherwise; returns the number of params
    checked.  This is the preflight ``rl_train --scenarios`` runs before
    paying for a full training compile.
    """
    from repro.envs import VmapWrapper

    venv = VmapWrapper(env, num_envs)
    step = jax.jit(venv.step)
    key = key if key is not None else jax.random.key(0)
    obs, state = venv.reset(key, params_list[0])
    action = venv.sample_action(key)
    step(key, state, action, params_list[0])  # warm-up entry
    with compile_guard(label, max_compiles=0):
        for p in params_list[1:]:
            step(key, state, action, p)
    n = cache_entries(step)
    if n != 1:
        raise RecompileError(
            label,
            [CompileEvent("step", f"{n} cache entries", "cache-size check")],
            1,
        )
    return len(params_list)
