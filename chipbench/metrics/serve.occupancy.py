"""Real rows over executed rows of the window's engine steps, in percent:
``SlotPool.occupancy`` as ``ConvServer`` counts it.  Moves serve_images_per_s."""


def read(run):
    occ = run.counters.get("occupancy")
    return None if not run.steps or occ is None else 100.0 * occ
