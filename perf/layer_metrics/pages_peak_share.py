"""pages_peak_share (%) - layer: KV pools. Highest number of pages mapped by
seated requests (entries of the pool's page table, sampled after every step
of the window) over ``num_pages``. Near 100 the pool, not the slots, sets
the batch. (The registry's ``paging/pages_in_use`` also counts pages only
the prefix trie still holds, which reads 100 % on any long run; those pages
are free for the asking, so they are left out here.)"""


def read(record):
    pages = record["samples"].get("pages_mapped", [])
    total = record["counters"].get("num_pages")
    if not pages or not total:
        return None
    return 100.0 * max(pages) / total
