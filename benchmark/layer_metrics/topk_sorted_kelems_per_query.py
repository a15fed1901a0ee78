"""Column executor: `executor.topk_keys_sorted` delta / queries, in thousands:
the keys the launches of `executor_program` handed to `lax.top_k` (the whole
padded plane where the top-k is one sort of it; what `ops.topk_blocks` leaves
where block maxima choose the blocks first). A program without the counter
reports nothing."""


def read(ctx):
    w = ctx["window"]
    keys = w["counters"].get("executor.topk_keys_sorted")
    if keys is None or not w["queries"]:
        return None
    return keys / 1e3 / w["queries"]
