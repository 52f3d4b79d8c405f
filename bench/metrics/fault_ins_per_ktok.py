"""Pages the KV pager faulted back in per 1,000 tokens generated in the
window: the ``fault_page_ins`` counter's delta times 1,000 over the
``tokens_generated`` delta.  Moves ``itl_p50_ms``: with a pool short of
the batch's pages, the rows evict each other's pages on every step."""


def read(run):
    c = run.counters
    if not c.get("tokens_generated") or "fault_page_ins" not in c:
        return None
    return 1e3 * c["fault_page_ins"] / c["tokens_generated"]
