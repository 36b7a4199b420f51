"""batch_width: mean number of queries per fused reader dispatch, from the
program's ``FlushStats.batch_of_split`` over the window's flushes."""


def read(rec):
    widths = [w for fs, _ in rec.get("flushes") or () for w in fs.batch_of_split]
    return sum(widths) / len(widths) if widths else None
