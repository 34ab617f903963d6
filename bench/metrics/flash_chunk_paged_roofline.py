"""flash_chunk_paged's share of its roofline (device trace, costs from the
rows and contexts of each step)."""


def read(run):
    from bench.readers import attention_roofline
    return attention_roofline(run, "flash_chunk_paged")
