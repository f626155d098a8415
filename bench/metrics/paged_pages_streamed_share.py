"""Share of the block tables' pages that the Pallas paged-attention
decode dispatches streamed (layer: kernels), from the program's tick log
(serving.telemetry.Telemetry ticks and their `kv_pages` and
`kv_pages_table`): 100 x the pages read over the pages the tables held.
A kernel that walks every table page reads 100%; one that reads only the
pages holding each slot's context reads what the contexts fill."""


def read(ctx):
    ticks = ctx.ticks
    if not ticks or any("kv_pages" not in a or "kv_pages_table" not in a
                        for _, _, a in ticks):
        return None
    table = sum(a["kv_pages_table"] for _, _, a in ticks)
    if table <= 0:
        return None
    return 100.0 * sum(a["kv_pages"] for _, _, a in ticks) / table
