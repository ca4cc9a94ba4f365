"""The engine cells' control: the K/V rows of the pool the window left, and
the drain steps' attention statistics, from the reference layer with every
operation rounded to the configuration's `control_dtype` (so its K/V are
stored in it), in the program's place."""
import contextlib


@contextlib.contextmanager
def in_place():
    from control import Rounded
    from substrates import engine as drv

    orig = drv.Driver._compare

    def compare(self, w, window_rows, drains):
        dt = self.cfg_file.get("control_dtype")
        if dt is not None:
            ctl = self.reference_layer(w, cast=Rounded(dt), operand=dt)
            window_rows = [(t, r, *ctl.kv_row(t, r))
                           for t, r, _, _ in window_rows]
            drains = [(t, att, ctl.attn_norm(t, att))
                      for t, att, _ in drains]
        return orig(self, w, window_rows, drains)

    drv.Driver._compare = compare
    try:
        yield
    finally:
        drv.Driver._compare = orig
