"""Drive a benchmark cell through the harness on the host CPU at a small
size: the look for a chip is skipped, the peaks of the chip the cell is
measured on stand in for the CPU's, and the configuration and traffic are
shrunk so that a test run holds them. Everything after the look for a chip
runs as in a chip run: set-up, the measured window, the check against the
plain reference and the result line."""
from __future__ import annotations

import contextlib
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

SMALL_ENGINE = dict(n_replicas=8, n_shards=2, pages_per_replica=16)
SMALL_WIDTHS = dict(hidden_size=128, num_attention_heads=4,
                    num_key_value_heads=2)
SMALL_SIM = dict(n_enclosures=4, n_windows=60, warmup=20)


def shrink(cell: dict) -> dict:
    conf = cell["config_file"]
    if conf["substrate"] == "engine":
        conf["engine"].update(SMALL_ENGINE)
        conf.update(SMALL_WIDTHS)
        # a quarter of the replicas: a quarter of the offered load
        cell["traffic_file"]["requests_per_step"] = 2.5
    else:
        conf.update(SMALL_SIM)
    return cell


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
