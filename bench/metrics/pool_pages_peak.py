"""Most pages of the KV pool in use at any decode step of the window
(``PagedKVStore.stats()``: pool pages less free pages, shared prefix
pages and the prefix cache counted once).  The server reserves the
cell's whole ``pool_pages`` on the device, used or not."""


def read(record):
    pages = record.host.get("pool_pages")
    if not pages:
        return None
    return max(pages)
