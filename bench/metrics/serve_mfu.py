"""Model FLOP utilisation of the whole step (``serve_mfu.<mix>``): the
model operations of every token that the steps inside the window
processed (prefill chunks and decoded tokens, attention over the live
context, from ``bench/counts.py``) / the window's seconds on the host
clock / the chip's bf16 peak. It takes the whole window, not the traced
part, so every admission in it counts."""

from bench import counts


def read(run):
    steps = run.records.get("window_steps") or []
    m = run.records["model"]
    flops = sum(counts.chunk_flops(m, off, n)
                for s in steps for off, n, _ in s["prefill"])
    flops += sum(counts.token_flops(m, pos) for s in steps for pos in s["decode"])
    seconds = run.records.get("window_s", 0.0)
    if not flops or seconds <= 0:
        return None
    return 100.0 * flops / seconds / run.peak["bf16_flops_per_s"]
