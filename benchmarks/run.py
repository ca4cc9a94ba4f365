"""Benchmark runner — one module per paper table/figure.

Prints ``name,value,derived`` CSV rows (assignment format). ``--quick``
shrinks sweeps; ``--only fig09`` runs a single module.

Figure modules are DISCOVERED, not listed: every ``fig*.py`` in this
directory registers itself under its figure key (``fig21_opcost.py`` →
``fig21``), so adding a figure benchmark never requires editing this file
— the hand-maintained table this replaces had already silently dropped
fig21. An unknown ``--only`` name fails loudly with the discovered
inventory instead of running nothing.
"""
from __future__ import annotations

import argparse
import importlib
import pathlib
import sys
import time

_DIR = pathlib.Path(__file__).resolve().parent
# non-figure modules keep their historical short names
_NAMED = {
    "engine": "engine_step",
    "manager": "manager_round",
    "kernels": "kernels_micro",
}


def discover() -> dict[str, str]:
    """name -> module stem, figures first (sorted), then the named extras."""
    mods = {}
    for p in sorted(_DIR.glob("fig*.py")):
        key = p.stem.split("_", 1)[0]
        if key in mods:
            raise RuntimeError(
                f"duplicate figure key {key!r}: {mods[key]}.py and {p.name}")
        mods[key] = p.stem
    mods.update(_NAMED)
    return mods


def _load(stem: str):
    if __package__:
        return importlib.import_module(f".{stem}", __package__)
    # direct-script invocation (`python benchmarks/run.py`): import the
    # sibling through the package so its relative imports still resolve
    sys.path.insert(0, str(_DIR.parent))
    try:
        return importlib.import_module(f"{_DIR.name}.{stem}")
    finally:
        sys.path.pop(0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args, _ = ap.parse_known_args()

    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()
    modules = discover()
    if args.only:
        if args.only not in modules:
            sys.exit(
                f"run.py: unknown benchmark {args.only!r}; available: "
                + " ".join(sorted(modules)))
        names = [args.only]
    else:
        names = list(modules)

    print("name,value,derived")
    failed = []
    for name in names:
        t0 = time.time()
        try:
            _load(modules[name]).main(quick=args.quick)
            print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
        except Exception as e:
            if args.only:
                raise  # a single requested module must fail loudly
            print(f"{name}_ERROR,{type(e).__name__},{e}")
            failed.append(name)
    if failed:
        # the suite keeps running past a broken module, but the process
        # still reports the breakage instead of exiting 0
        sys.exit(f"run.py: {len(failed)} benchmark(s) failed: "
                 + " ".join(failed))


if __name__ == "__main__":
    main()
