"""Of the block pairs the windowed flash kernels run a head, forward and
backward, the share that an edge crosses (the causal diagonal or the
window's lower edge), in percent: the part of the kernels' work spent on
tiles that are partly masked, which the choice of blocks decides. Counted
by the program's own ``ops/flash_attention.block_schedule`` at the blocks
it fits to the sliding layers' calls (``families/<family>.kernel_work``'s
``window_shape``: the sequence and the window). Left out when the family
states no such shape or the program has no windowed kernel."""

LAYER, UNIT, MOVES = "kernels", "%", "step_ms"


def read(run):
    from horovod_tpu.ops import flash_attention as fa

    shape = (run["kernel_work"] or {}).get("window_shape")
    blocks = getattr(fa, "WINDOW_BLOCKS", None)
    if shape is None or blocks is None:
        return None
    seq, window = shape
    counts = [fa.block_schedule(seq, seq, *pair, window=window)
              for pair in blocks]
    crossed = sum(c["diagonal"] for c in counts)
    return 100.0 * crossed / (crossed + sum(c["interior"] for c in counts))
