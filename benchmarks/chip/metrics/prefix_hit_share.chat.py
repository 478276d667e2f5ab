"""Share of prompt tokens served from the prefix cache rather than
prefilled: shared_prompt_tokens / (shared_prompt_tokens +
prefill_tokens), from the engine's counters over the run."""

LAYER = "serve scheduler"
UNIT = "%"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def read(rec):
    e = rec["engine"]
    total = e["shared_prompt_tokens"] + e["prefill_tokens"]
    return 100.0 * e["shared_prompt_tokens"] / total if total else None
