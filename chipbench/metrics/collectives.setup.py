"""How many ``psum`` and ``ppermute`` calls the program traced into its
sharded projector pair (the counter ``distributed.collectives`` of
``repro.runtime.obs``): 0 for a layout whose shards exchange nothing.
None for a program without that counter.  Read after the window, like
the other ``.setup`` readers: the pair is traced in set-up alone."""


def read(r):
    try:
        from repro.runtime import obs
    except ImportError:
        return None
    return obs.snapshot()["counters"].get("distributed.collectives")
