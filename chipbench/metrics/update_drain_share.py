"""Share of the window the host waits, inside RigL's topology update, for
the update step's device work: the seconds of the program's
``repro.refresh_pack.drain`` region (``training/steps.py::refresh_superset``,
the first host read of the update step's outputs) over the window (%)."""
from chipbench import program_spans


def read(ctx):
    update = program_spans.window_update(ctx)
    if update is None:
        return None
    return 100.0 * update[1] / ctx["window"]["seconds"]
