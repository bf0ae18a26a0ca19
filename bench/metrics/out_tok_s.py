"""Output tokens completed in the window over the window's length, on the
host clock (every token of every request, first tokens included)."""


def read(record):
    tokens = record.host.get("tokens")
    if not tokens:
        return None
    return tokens / record.window_s
