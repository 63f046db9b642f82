"""Shared on-chip timing harness for the perf/ scripts.

Methodology: a trial runs INNER chained iterations INSIDE one jit
(`lax.fori_loop` over a scalar token computed from the full output)
and pays one dispatch + one forced `float()` readback, so per-dispatch
host cost is amortized and nothing can be elided or overlap. Run
configs interleaved and compare medians; a change to the fencing
belongs HERE, not copy-pasted per script.
"""

import ast
import os
import statistics
import sys
import time

# perf/ scripts run as `python perf/<script>.py` from the repo root;
# make the package importable without PYTHONPATH.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from triton_client_tpu.utils.compilation_cache import enable_persistent_cache

enable_persistent_cache()  # perf/ and the entry points share one compile bill

import jax
import jax.numpy as jnp


def tokify(*outs) -> jnp.ndarray:
    """Scalar fencing token depending on every output element."""
    return sum(
        jnp.sum(o) * 1e-12 for o in jax.tree.leaves(outs)
    ).astype(jnp.float32)


class TimedHostSyncError(AssertionError):
    """A timed region contains a TPL3xx host sync (tpulint)."""


def assert_timed_region_clean(*fns, allow=()) -> None:
    """Static TPL3xx gate over timed-region callables.

    Runs tpulint's host-sync call-graph (analysis.rules.hostsync) over
    each callable's source with the callable itself as the reachability
    root, and raises :class:`TimedHostSyncError` on any finding — so a
    future profiling script cannot accidentally time a ``np.asarray``/
    ``.item()``/``block_until_ready`` inside the region it claims is
    device-only (the fencing ``float(tok)`` readback belongs OUTSIDE
    ``one``, in run_trials, where the methodology accounts for it).

    ``allow``: TPL codes to ignore (e.g. ``("TPL302",)`` for a region
    that fences deliberately). Callables whose source is unavailable
    (builtins, REPL lambdas) are skipped — unverifiable, not fatal —
    and ``TPULINT_PERF_SKIP=1`` bypasses the gate wholesale.
    """
    if os.environ.get("TPULINT_PERF_SKIP"):
        return
    import inspect
    import textwrap

    from triton_client_tpu.analysis.engine import load_source
    from triton_client_tpu.analysis.rules.hostsync import (
        _sync_calls_in,
        check_reachable,
    )

    problems: list[str] = []
    for fn in fns:
        target = inspect.unwrap(fn)
        try:
            src = textwrap.dedent(inspect.getsource(target))
            name = getattr(target, "__name__", "")
        except (OSError, TypeError):
            continue
        label = f"<timed region {name or 'lambda'}>"
        if name and name != "<lambda>":
            try:
                pkg = load_source(src, path=label)
            except SyntaxError:
                continue
            problems.extend(
                f.render()
                for f in check_reachable(pkg, [name])
                if f.code not in allow
            )
        else:
            # a bare lambda: getsource returns the whole enclosing
            # statement — pull the first Lambda node out of it and scan
            # its body directly with the same sync-call detector
            tree = None
            for candidate in (src, src.strip().rstrip(",")):
                try:
                    tree = ast.parse(candidate)
                    break
                except SyntaxError:
                    continue
            if tree is None:
                continue
            lam = next(
                (n for n in ast.walk(tree) if isinstance(n, ast.Lambda)), None
            )
            if lam is not None:
                # wrap: the body may itself be the sync call, and the
                # detector inspects children of the node it is given
                wrapped = ast.Expr(value=lam.body)
                problems.extend(
                    f"{label}:{call.lineno}: {code} {desc}"
                    for call, code, desc in _sync_calls_in(wrapped)
                    if code not in allow
                )
    if problems:
        raise TimedHostSyncError(
            "host sync inside a timed region (tpulint TPL3xx; move the "
            "readback outside the region or pass allow=/set "
            "TPULINT_PERF_SKIP=1):\n" + "\n".join(problems)
        )


def compile_looped(one, inner: int):
    """jit of `inner` chained iterations of ``one(tok) -> tok``; warmed.

    The timed region is ``one``: tpulint's host-sync gate runs over it
    first, so a host readback cannot silently hide inside the loop the
    methodology assumes is device-only."""
    assert_timed_region_clean(one)
    looped = jax.jit(
        lambda tok: jax.lax.fori_loop(0, inner, lambda i, t: one(t), tok)
    )
    tok = jnp.float32(0.0)
    for _ in range(2):
        tok = looped(tok)
    float(tok)
    return looped


def run_trials(cases, inner: int, outer: int = 2, trials: int = 6) -> dict:
    """cases: [(name, looped_jit)]. Interleaved rounds; returns
    {name: median ms-per-inner-iteration} and prints each line."""
    acc = {name: [] for name, _ in cases}
    for _ in range(trials):
        for name, step in cases:
            tok = jnp.float32(0.0)
            t0 = time.perf_counter()
            for _ in range(outer):
                tok = step(tok)
            float(tok)
            acc[name].append((time.perf_counter() - t0) * 1e3 / (outer * inner))
    out = {}
    for name, _ in cases:
        out[name] = statistics.median(acc[name])
        print(f"{name:46s} {out[name]:8.3f} ms", file=sys.stderr)
    return out


def timed(name, one, inner: int = 10, outer: int = 2, trials: int = 6) -> float:
    """One-off: compile + run a single case."""
    looped = compile_looped(one, inner)
    return run_trials([(name, looped)], inner, outer, trials)[name]
