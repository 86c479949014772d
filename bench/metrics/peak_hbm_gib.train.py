"""Peak device memory of the training run (``peak_bytes_in_use`` on the
fullest chip, read after the window), in GiB. It moves train_tokens_per_s
only through the batch it leaves room for."""


def read(ctx):
    return ctx.memory_peak_bytes / 2**30 if ctx.memory_peak_bytes else None
