"""Share of the traced window in which no operation ran on the device, in
percent, averaged over the chips.  Moves serve_images_per_s."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
