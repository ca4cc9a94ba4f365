"""Drive a benchmark cell through the harness on the host CPU at a small
size: the look for a chip is skipped, the peaks of the chip the cell is
measured on stand in for the CPU's, and the configuration and traffic are
shrunk, by the `small(cell)` hook of the cell's substrate, so that a test
run holds them. Everything after the look for a chip runs as in a chip run:
set-up, the measured window, the check against the plain reference and the
result line."""
from __future__ import annotations

import contextlib
import importlib
import io
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402
import peaks  # noqa: E402

def shrink(cell: dict) -> dict:
    """The loaded ``cell`` at the small size of its substrate's hook."""
    sub = cell["config_file"]["substrate"]
    small = getattr(importlib.import_module(f"substrates.{sub}"), "small",
                    None)
    if small is None:
        raise NotImplementedError(
            f"substrate {sub!r} defines no small(cell) in "
            f"bench/substrates/{sub}.py")
    return small(cell)


def run(monkeypatch, workload: str, seed: int, seconds: float = 1.0,
        trace: int = 0, edit=None) -> dict:
    """The result line of one small CPU run of ``workload``; ``edit``
    may change the loaded cell further (e.g. its configuration)."""
    import jax

    load = harness.load_cell

    def small(name):
        cell = shrink(load(name))
        return edit(cell) if edit else cell

    monkeypatch.setattr(harness, "load_cell", small)
    monkeypatch.setattr(harness, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    out = io.StringIO()
    jax.clear_caches()
    with contextlib.redirect_stdout(out):
        harness.main(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
    jax.clear_caches()
    return json.loads(out.getvalue().strip().splitlines()[-1])
