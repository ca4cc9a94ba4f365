"""The simulator cells' control: every `simulate` call's statistics from
the reference fluid model with every operation rounded to the
configuration's `control_dtype`, in the program's place."""
import contextlib


@contextlib.contextmanager
def in_place():
    import numpy as np
    from control import Rounded
    from reference import sim as ref
    from substrates import sim as drv

    orig = drv.Driver._call

    def call(self, rows, arr):
        dt = self.conf.get("control_dtype")
        if dt is None:
            return orig(self, rows, arr)
        out = ref.simulate(self.params, rows, arr, self.e, self.warmup,
                           self.window_s, cast=Rounded(dt))
        out["ring_borrowed"] = out["ring_borrowed"][:, None]
        out["ring_spare"] = out["ring_spare"][:, None]
        return {k: np.asarray(v, np.float64) for k, v in out.items()}

    drv.Driver._call = call
    try:
        yield
    finally:
        drv.Driver._call = orig
