"""Peak device memory of the run, ``memory_stats()["peak_bytes_in_use"]``
on the fullest chip, read after the window and before the reference
runs (a run is a new process, so the lifetime peak is the cell's)."""
LAYER = "device"
UNIT = "GB"
MOVES = "serve_tokens_per_s"


def read(run):
    return run["peak_bytes"] / 1e9 if run["peak_bytes"] else None
