"""Share of the prompt tokens prefilled in the window that the prefix
index let the engine skip (its ``saved_prefill_tokens`` counter)."""


def read(rec, trace):
    steps = rec.window_steps()
    prompt = sum(rec.requests[i].prompt_len for s in steps
                 for i in s.prefilled)
    if not prompt:
        return None
    return 100.0 * sum(s.saved for s in steps) / prompt
