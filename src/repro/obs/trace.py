"""Trace annotations: named phases for profiles, zero-cost when disabled.

:func:`annotate` marks a region with ``jax.named_scope`` (names the XLA ops
traced inside it, so phases show up in a profile and in HLO metadata) plus
``jax.profiler.TraceAnnotation`` (marks the host thread, so host-side phases
show as spans).  Annotations are **disabled by default** and the disabled
path is a bare ``yield``: the compiled program and its op metadata are the
same with the subsystem off as without the ``annotate`` calls
(``tests/obs/test_trace.py::test_annotate_is_a_bare_noop_when_disabled``,
and ``tests/obs/test_scopes.py`` for every scope of the env step and the
PPO update).

Enable them either with :func:`enable_trace_annotations` /
``REPRO_TRACE=1``, or implicitly via :func:`trace_session`, which wraps
``jax.profiler.start_trace``/``stop_trace`` and yields a perfetto-viewable
``*.trace.json.gz`` (open at https://ui.perfetto.dev).  ``rl_train
--profile DIR`` is the CLI surface; ``bench/run.py --trace 1`` turns them on
for the benchmark's traced runs.

Phase-name catalog (see ``docs/observability.md``).  An indented name sits
inside the one above it.  The last column names the ``bench/metrics``
reader that reads the scope; ``env/*`` and ``wrap/*`` are also read as
wholes (``env_step_ns.*``, ``env_step_roofline.sim``: all of ``env/``;
``wrap_self_ns.sim``: ``wrap/`` outside ``env/``).

=========================  ==========================================  ========================
``env/decode``             action decoding (direct / delta modes)      ``env/`` only
``env/apply_actions``      request: Eq. 5 constrained currents         ``env/`` only
``env/allocate``           feeder-cap curtailment                      ``env/`` only
``env/fused_transition``   fused kernel route (request..deliver)       none: no cell runs it
``env/charge_cars``        deliver: energy integration + V2G debt      ``env/`` only
``env/depart_arrive``      departures, arrivals, rejections            ``env/`` only
  ``env/departures``       departure masks and state clears            ``env/`` only
  ``env/arrive_count``     rate lookup, Poisson draw, FCFS rank        ``env/`` only
  ``env/draw_cars``        every port's profile draws                  ``env/`` only
    ``env/draw_model``     car-model draw (``random.choice``)          ``draw_model_ns.sim``
    ``env/draw_soc0``      arrival SoC draw (``random.beta``)          ``env/`` only
  ``env/car_lookup``       per-port model-table gathers                ``car_lookup_ns.sim``
  ``env/place_cars``       user profiles and the state writes          ``env/`` only
``env/reward``             settle: Eq. 1-3 reward, grid penalties      ``env/`` only
``env/observe``            the step's observation build                ``env/`` only
  ``env/price_window``     price-horizon gather                        ``price_window_ns.train``
``wrap/<Wrapper>``         each wrapper layer's step (Vmap, AutoReset  ``wrap_self_ns.sim``
                           reset and select, Log, FleetAdapter)
``ppo/rollout``            the rollout scan                            ``ppo_rollout_ms``
``ppo/gae``                advantage estimation                        none
``ppo/update``             minibatch epochs                            ``ppo_update_ms``
  ``ppo/shuffle``          permutation, gather, minibatch reshapes     ``ppo_shuffle_ms``
  ``ppo/optim``            AdamW update and apply                      none
``ppo/mlp``                actor-critic forward; backward ops read     ``ppo_update_mlp_ms``
                           ``transpose(jvp(ppo/mlp))``                 (ops also in ``ppo/update``)
``ppo/logprob``            factorised categorical log-probability      ``ppo_logprob_ms``
``eval/rollout``           evaluation episodes                         none
``eval/serve``             batched policy inference step               none
``profile/*``              ``rl_train --profile`` host windows         none
=========================  ==========================================  ========================

No name is a prefix of another: the benchmark's reducer matches scopes by
prefix.  ``env/price_window`` is set by the step's observation only, so
the copy that ``reset`` (under ``AutoReset``) computes stays outside
``env/*``.
"""
from __future__ import annotations

import contextlib
import glob
import os
from typing import Iterator

import jax

_enabled: bool = os.environ.get("REPRO_TRACE", "0").lower() in ("1", "true", "yes")


def trace_annotations_enabled() -> bool:
    """Whether :func:`annotate` currently emits named scopes."""
    return _enabled


def enable_trace_annotations(on: bool = True) -> bool:
    """Toggle annotations globally; returns the previous setting.

    Enable *before* building/jitting the functions you want annotated:
    ``named_scope`` acts at trace time, so already-compiled programs keep
    their unannotated cache entries.
    """
    global _enabled
    prev, _enabled = _enabled, bool(on)
    return prev


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Mark a phase: ``with annotate("env/charge_cars"): ...``.

    Inside jitted code this names the ops traced under it (visible in
    profiles and HLO metadata); on the host it opens a profiler span.
    Disabled (the default) it is a bare yield — no named_scope, no
    TraceAnnotation, no program change.
    """
    if not _enabled:
        yield
        return
    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield


def _start_trace(log_dir: str, python_tracer: bool) -> None:
    """``jax.profiler.start_trace``, optionally without the Python-call
    tracer.

    jax's default profiler options record EVERY python call (``isinstance``,
    ``len``, …) while tracing runs under the session — for a jit-heavy
    program that is ~100k events per second of trace time and dwarfs the
    phase spans we care about.  Level 0 keeps host ``TraceAnnotation`` spans
    and device/op events.
    """
    opts = jax.profiler.ProfileOptions()
    if not python_tracer:
        opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


@contextlib.contextmanager
def trace_session(
    log_dir: str,
    enable_annotations: bool = True,
    keep_xplane: bool = True,
    python_tracer: bool = False,
) -> Iterator[str]:
    """Profile a region: annotations on, ``jax.profiler`` tracing to
    ``log_dir``.  Yields ``log_dir``; on exit the trace is flushed and the
    annotation toggle restored.

    The session only annotates functions *traced inside it* (or after
    :func:`enable_trace_annotations`); pre-compiled cache entries keep
    their old names.  Find the trace with :func:`latest_trace`.

    Keep the traced region SMALL — one representative update / a handful of
    env steps.  The CPU tracer records every op execution, so tracing a full
    training run produces multi-GB event buffers and a multi-minute
    ``stop_trace``.  ``rl_train --profile`` therefore traces a one-update
    probe, not the real run.

    ``keep_xplane=False`` deletes the bulky ``*.xplane.pb`` sidecar after
    the trace is flushed, keeping only the perfetto ``*.trace.json.gz`` —
    use for CI artifacts (see :func:`check_trace_budget`).

    ``python_tracer=True`` additionally records every Python call (jax's
    upstream default) — an order of magnitude more events; only useful when
    hunting host-side python overhead.
    """
    os.makedirs(log_dir, exist_ok=True)
    prev = enable_trace_annotations(enable_annotations)
    _start_trace(log_dir, python_tracer)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()
        enable_trace_annotations(prev)
        if not keep_xplane:
            for p in glob.glob(
                os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True
            ):
                os.remove(p)


def latest_trace(log_dir: str) -> str | None:
    """Newest perfetto trace file under ``log_dir`` (None if no trace)."""
    paths = glob.glob(
        os.path.join(log_dir, "**", "*.trace.json.gz"), recursive=True
    )
    return max(paths, key=os.path.getmtime) if paths else None


def trace_bytes(log_dir: str) -> int:
    """Total size of all profiler output under ``log_dir``."""
    total = 0
    for root, _, files in os.walk(log_dir):
        for f in files:
            total += os.path.getsize(os.path.join(root, f))
    return total


def check_trace_budget(log_dir: str, max_kb: int = 8192, verbose: bool = False) -> int:
    """Artifact-size guard for CI: profiler output must stay shippable.

    Raises ``RuntimeError`` if the trace directory exceeds ``max_kb``;
    returns the total size in bytes.  Mirrors the vendored-fixture budget
    guard (``repro.data.ingest.check_fixture_budget``) for trace output.
    """
    total = trace_bytes(log_dir)
    if verbose:
        print(f"[obs] trace artifacts under {log_dir}: {total/1024:.1f} KB "
              f"(budget {max_kb} KB)")
    if total > max_kb * 1024:
        raise RuntimeError(
            f"trace output in {log_dir} is {total/1024:.0f} KB, over the "
            f"{max_kb} KB artifact budget — lower the traced region size "
            "(fewer updates/steps under trace_session)"
        )
    return total
