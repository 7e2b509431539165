"""Share of the window's wall time spent in RigL's topology updates:
``rigl_step`` and ``refresh_pack``, from the harness's host spans (%)."""


def read(ctx):
    w = ctx["window"]
    return 100.0 * w["update_s"] / w["seconds"]
