"""Out-of-core streaming: store fetches (refined extents, ``blocks``) per wave over the window, summed over the shards of a sharded collection."""


def read(ctx) -> float | None:
    blocks, calls = ctx.window.delta("blocks"), ctx.window.delta("calls")
    if blocks is None or not calls:
        return None
    return blocks / calls
