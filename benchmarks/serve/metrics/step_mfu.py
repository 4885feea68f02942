"""Model FLOPs of every token the window's ticks computed (prefill
positions past the leased prefix, and decoded tokens) over the ticks' host
wall time times the chip's bf16 peak, in %."""


def read(run):
    work, wall = 0.0, 0.0
    for t in run.ticks:
        wall += t["end"] - t["start"]
        for prompt, skip in t["admitted"]:
            work += run.model.prefill_flops(run.sizes, skip, prompt)
        for in_pages in t["decoded"]:
            work += run.model.decode_token_flops(run.sizes, in_pages)
    if not wall:
        return None
    return 100.0 * work / (wall * run.peaks["bf16_flops_per_s"])
