"""Device memory the index holds as uploaded, in bits per posting: 8 x
(bytes in use after the upload - bytes in use before it) over the corpus's
postings, from the device allocator's ``memory_stats``."""


def read(run):
    return 8.0 * run.index_bytes / run.postings if run.postings else None
