"""Share of admitted prompt tokens read from leased prefix pages, in %.

From the program's exact counter ``prefix_prefill_tokens_skipped`` over the
prompt tokens the window admitted (harness count)."""


def read(run):
    prompt = sum(p for t in run.ticks for p, _ in t["admitted"])
    if not prompt:
        return None
    return 100.0 * run.counter_delta("prefix_prefill_tokens_skipped") / prompt
