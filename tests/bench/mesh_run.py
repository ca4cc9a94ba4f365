"""Run the engine cell on four virtual CPU devices at a small size, in a
process of its own (the device count is fixed when JAX starts):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python tests/bench/mesh_run.py [--no-exchange]

prints the result line. `--no-exchange` breaks the timed path: in the
sharded step every chip gathers only its own copy of the exchange summary,
as if the collective between chips were left out."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import cpu_run  # noqa: E402


class _Patch:
    """The part of pytest's monkeypatch that `cpu_run.run` uses."""

    def __init__(self):
        self.undo = []

    def setattr(self, obj, name, value):
        self.undo.append((setattr, obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def setitem(self, d, key, value):
        d[key] = value


def _no_exchange():
    import jax
    import jax.numpy as jnp
    from repro.serving import engine as E

    make = E.make_sharded_step

    def make_broken(cfg, mesh=None):
        fn = make(cfg, mesh)

        def step(state, arrivals):
            real = jax.lax.all_gather
            jax.lax.all_gather = lambda x, axis, **kw: jnp.broadcast_to(
                x, (cfg.n_shards,) + x.shape)
            try:
                return fn(state, arrivals)
            finally:
                jax.lax.all_gather = real

        return step

    E.make_sharded_step = make_broken


def main():
    import json

    if "--no-exchange" in sys.argv:
        _no_exchange()

    def four(cell):
        cell["chips"] = 4
        cell["config_file"]["engine"]["n_shards"] = 4
        return cell

    line = cpu_run.run(_Patch(), "engine.skew", 3_000_000_031, seconds=2.0,
                       edit=four)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
