"""Read a training cell's reference check again under a control, to see what
each limit in the configuration's ``tolerances`` tells apart. It is the
benchmark's one command with one thing replaced before it starts, so the
weights, the window and the check are those of a plain run:

    python3 benchmarks/tools/control_readings.py <control> --workload <cell> --seed <n> --seconds 30 --trace 0

``float8``        the REFERENCE's matrices rounded to float8_e4m3fn, one
                  precision below a system that computes in bfloat16 (the
                  upper reading of a limit; any cell whose runner is
                  ``train_causal``). The result line's ``correct`` should be
                  false; standard error's ``bench: notes`` line has the
                  readings.
``left_out``      the plain check, then the check repeated with one term of
                  the REFERENCE left out each time (``bench: leftout`` lines
                  on standard error, ``correct`` beside each). The terms are
                  those of the delta-rule expert decoder's reference.
``scan_bfloat16`` the PROGRAM's delta rule with its triangular solve and its
                  carried state in bfloat16 (``_delta_rule``'s
                  ``state_dtype``).

The cell is found by name as ``run.py`` finds it; nothing here names one, and
jax is not imported before ``run.py`` has set its environment.
"""
from __future__ import annotations

import functools
import json
import os
import runpy
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _with_check(ref, check):
    """The reference module ``ref`` with another ``check``."""
    return types.SimpleNamespace(**{**vars(ref), "check": check})


def float8():
    from benchmarks.lib import model

    plain = model.reference

    def rounded(config):
        ref = plain(config)

        def check(params, batch, at, sizes):
            import jax
            import jax.numpy as jnp

            low = jax.tree.map(
                lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                if a.ndim >= 2 else a, params)
            return ref.check(low, batch, at, sizes)

        return _with_check(ref, check)

    model.reference = rounded


def _left_out_terms():
    """name -> (edit of the parameters, edit of the sizes)."""
    import jax.numpy as jnp

    def blocks(edit, only=None):
        def on(p):
            return dict(p, blocks=[
                edit(dict(b)) if only is None or only in b else b
                for b in p["blocks"]])
        return on

    def put(key, value):
        def edit(b):
            b[key] = value(b[key])
            return b
        return edit

    def same(x):
        return x

    zero = jnp.zeros_like
    return {
        "held expert 0": (blocks(put("experts", lambda e: dict(
            e, down=e["down"].at[0].set(0.0)))), same),
        "shared expert": (blocks(put("shared", lambda e: dict(
            e, down=zero(e["down"])))), same),
        "selection bias": (blocks(put("router_bias", zero)), same),
        "beta's factor 2": (same, lambda s: dict(s, neg_eigval=False)),
        "decay (g = 0)": (blocks(put(
            "A_log", lambda a: jnp.full_like(a, -30.0)), "conv"), same),
        "second tap": (blocks(put("conv", lambda c: {
            n: w.at[1].set(0.0) for n, w in c.items()}), "conv"), same),
        "output gate (0.5)": (blocks(put("g_up", zero), "conv"), same),
        "attention gate (0.5)": (blocks(put("gate", zero), "gate"), same),
    }


def left_out():
    from benchmarks.lib import model
    from benchmarks.lib.runners import train_causal

    agree, plain = train_causal._agrees_with_reference, model.reference

    def repeated(cell, cfg, params, sizes, notes):
        ok = agree(cell, cfg, params, sizes, notes)
        for name, (on_params, on_sizes) in _left_out_terms().items():
            def without(config):
                ref = plain(config)
                return _with_check(ref, lambda p, b, a, s: ref.check(
                    on_params(p), b, a, on_sizes(s)))

            model.reference = without
            n = {}
            verdict = agree(cell, cfg, params, sizes, n)
            print("bench: leftout", json.dumps(
                {"term": name, "correct": verdict, **n["reference"]}),
                file=sys.stderr, flush=True)
        model.reference = plain
        return ok

    train_causal._agrees_with_reference = repeated


def scan_bfloat16():
    from benchmarks.lib.runners import train_causal

    run = train_causal.run

    def lowered(cell):
        import jax.numpy as jnp
        from deeplearning4j_tpu.models import delta_decoder

        delta_decoder._delta_rule = functools.partial(
            delta_decoder._delta_rule, state_dtype=jnp.bfloat16)
        return run(cell)

    train_causal.run = lowered


CONTROLS = {f.__name__: f for f in (float8, left_out, scan_bfloat16)}


def main() -> None:
    if len(sys.argv) < 2 or sys.argv[1] not in CONTROLS:
        raise SystemExit(f"usage: control_readings.py "
                         f"{{{'|'.join(CONTROLS)}}} <run.py's arguments>")
    CONTROLS[sys.argv[1]]()
    run = os.path.join(ROOT, "benchmarks", "run.py")
    sys.argv = [run] + sys.argv[2:]
    runpy.run_path(run, run_name="__main__")


if __name__ == "__main__":
    main()
