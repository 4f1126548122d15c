"""Share (%) of the paged LAMP kernels' KV grid steps that touch a live
block, over the window's steps: each step's rows (start, live queries), as
the recorder saw them, walked by the program's own grid rule
(`grid_blocks` in kernels/paged_attention.py, one pass, one head, one
layer) at the engine's row bucket; pad rows are walked, never live. A
program without that rule reads nothing."""

from bench import harness


def read(ctx):
    try:
        from repro.kernels.paged_attention import grid_blocks
    except ImportError:
        return None
    e = ctx.config["engine"]
    if not ctx.rec.steps:
        return None
    cap = e["max_prefill_batch"] + e["max_decode_batch"]
    bs = int(e["block_size"])
    n_max = -(-int(e["max_model_len"]) // bs)
    win = harness.program_config(ctx.config).window
    live = walked = 0
    for rows, _ in ctx.rec.steps:
        if not rows:
            continue
        n = len(rows)
        lv, wk = grid_blocks([c for c, _ in rows], [q for _, q in rows], n,
                             harness._bucket(n, cap), n_max, bs, window=win)
        live += lv
        walked += wk
    return 100.0 * live / walked if walked else None
