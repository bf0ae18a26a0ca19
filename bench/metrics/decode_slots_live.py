"""Mean number of the batch's slots that hold a request in each decode
step of the window: how full the server keeps its batch.  Below the
server's capacity it is the offered rate times the time a request stays
(Little's law), so a faster server reads lower; above it, the batch
fills."""


def read(record):
    lengths = record.host.get("decode_lengths")
    if not lengths:
        return None
    return sum(len(call) for call in lengths) / len(lengths)
