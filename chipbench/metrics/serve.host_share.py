"""Share of the traced window the host spends inside the program's
``serve.step`` spans but outside their ``serve.forward`` spans, in percent:
the host's part of the device's idle share.  Moves serve_images_per_s."""
from chipbench import program_spans


def read(run):
    return program_spans.host_share(run)
