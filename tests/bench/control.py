"""Readings for the limits of `correct`: the program's and the control's.

The control is what the comparison has to refuse: the plain reference,
computed in bfloat16, in the program's place (the nearest precision below
the float32 that each configuration states). Each substrate's control is a
file of its own, `controls/<substrate>.py`, whose `in_place()` makes the
substitution (the engine's K/V rows and attention statistics, the
simulator's per-call statistics); `reference_in_place` enters them all.

    python tests/bench/control.py --workload engine.skew --variant control \
        --seeds 1 2 3 --seconds 3

runs the harness once per seed in this one process, on the chip it finds,
and prints one `READING {json}` line per seed with the numbers compared.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
CONTROLS = pathlib.Path(__file__).resolve().parent / "controls"
for _p in (ROOT / "bench", ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def edit_control(cell: dict) -> dict:
    """Switch a loaded cell to its control."""
    cell["config_file"]["control_dtype"] = "bfloat16"
    return cell


class Rounded:
    """Array cast for the control: float32 storage whose every operation's
    result is rounded to ``dtype`` (bfloat16), as on hardware that computes
    and stores in it."""

    def __init__(self, dtype):
        import ml_dtypes
        import numpy as np

        rdt = getattr(ml_dtypes, dtype)

        class Arr(np.ndarray):
            def __array_ufunc__(self, ufunc, method, *inputs, **kw):
                return _wrap(getattr(ufunc, method)(*_plain(inputs), **kw))

            def __array_function__(self, func, types, args, kw):
                return _wrap(func(*_plain(args), **_plain(kw)))

        def _plain(x):
            if isinstance(x, Arr):
                return x.view(np.ndarray)
            if isinstance(x, (list, tuple)):
                return type(x)(_plain(v) for v in x)
            if isinstance(x, dict):
                return {k: _plain(v) for k, v in x.items()}
            return x

        def _wrap(r):
            if isinstance(r, tuple):
                return tuple(_wrap(v) for v in r)
            if isinstance(r, (np.ndarray, np.floating)) \
                    and np.asarray(r).dtype.kind == "f":
                return np.asarray(r).astype(rdt).astype(np.float32).view(Arr)
            return r

        self._wrap = _wrap

    def __call__(self, x):
        import numpy as np

        return self._wrap(np.asarray(x, np.float32))


@contextlib.contextmanager
def reference_in_place():
    """While active, a driver whose configuration carries `control_dtype`
    takes what it compares from the plain reference in that precision
    instead of the program: every control under `controls/` is in place."""
    from harness import load_module

    with contextlib.ExitStack() as stack:
        for path in sorted(CONTROLS.glob("*.py")):
            stack.enter_context(load_module(path, "control").in_place())
        yield


def readings(workload: str, seeds, seconds: float, variant: str):
    """Run the harness per seed in this process; yield (seed, line)."""
    import harness

    load = harness.load_cell

    def cell(name):
        c = load(name)
        return edit_control(c) if variant == "control" else c

    harness.load_cell = cell
    try:
        with reference_in_place():
            for seed in seeds:
                out = io.StringIO()
                with contextlib.redirect_stdout(out):
                    harness.main(["--workload", workload, "--seed",
                                  str(seed), "--seconds", str(seconds),
                                  "--trace", "0"])
                yield seed, json.loads(out.getvalue().strip()
                                       .splitlines()[-1])
    finally:
        harness.load_cell = load


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variant", choices=("program", "control"),
                    required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    for seed, line in readings(args.workload, args.seeds, args.seconds,
                               args.variant):
        print("READING " + json.dumps({
            "workload": args.workload, "variant": args.variant,
            "seed": seed, "correct": line["correct"],
            "checks": line["checks"]}), flush=True)


if __name__ == "__main__":
    main()
