"""Device memory the index holds once the window has drained, in bits per
posting: what ``bits_per_posting`` reads plus whatever serving built or
cached on the device (the engine's round cache, a lazily built
representation), from the device allocator's ``memory_stats``."""


def read(run):
    return 8.0 * run.serving_bytes / run.postings if run.postings else None
