"""Blocks the window group holds over what whole-sequence tables would
hold: `serving/kv_block_steps{group=window}` over `{group=full}` (blocks
held, summed over the window's decode steps; both groups have one block
size, and the full group keeps every sequence whole).  100% is an
allocator that bounds nothing.  Source: program counters."""


def compute(ctx):
    c = ctx["counters"]
    full = c.get("serving/kv_block_steps{group=full}", 0)
    if not full or "serving/kv_block_steps{group=window}" not in c:
        return None
    return 100.0 * c["serving/kv_block_steps{group=window}"] / full
