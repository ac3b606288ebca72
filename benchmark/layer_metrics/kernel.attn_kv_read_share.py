"""Share of the reserved KV cache that a layer's decode attention reads
(%): the program's counters ``attn.kv_blocks_read`` over
``attn.kv_blocks_reserved`` across the window (blocks of the decode
kernel's rows: over every slot and decode step, the blocks of the
kernel's own range up to the frontier the slot was dispatched with,
against slots x window / block x steps). The gauge ``attn.decode_kernel``
says what the program's attention chose when the decode programs were
traced: the kernel that reads only those blocks, or (0) the sweep of the
whole reservation, whatever the frontiers: 100. A program without the
counters or the gauge gives nothing."""
from counters import series_delta


def read(ctx):
    read_blocks = series_delta(ctx, "attn.kv_blocks_read")
    reserved = series_delta(ctx, "attn.kv_blocks_reserved")
    kernel = ctx["after"]["status"]["metrics"].get("attn.decode_kernel")
    if read_blocks is None or not reserved or kernel is None:
        return None
    if not kernel.get("value"):
        return 100.0
    return 100.0 * read_blocks / reserved
