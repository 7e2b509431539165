"""Share of the window spent refreshing the pack once the update step's
outputs are in hand: the program's ``repro.refresh_pack`` region less its
``.drain`` (the superset redraw's dispatch, the masks' fetch to the host,
numpy packing, ``validate_pack`` and the upload) over the window (%)."""
from chipbench import program_spans


def read(ctx):
    update = program_spans.window_update(ctx)
    if update is None:
        return None
    refresh, drain = update
    return 100.0 * (refresh - drain) / ctx["window"]["seconds"]
