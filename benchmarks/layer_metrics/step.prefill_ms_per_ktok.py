"""Device time of the prefill programs (solo and batched) in the traced
span per thousand prompt tokens prefilled in it. Prompt tokens are those
of the requests whose first token reached the client inside the span
(the generator's log; the engine has no prompt-token counter)."""

MODULES = ("jit_prefill_impl", "jit_batch_prefill_impl")


def read(sources):
    trace, span = sources.get("trace"), sources.get("trace_span")
    if not trace or not span:
        return None
    secs = sum(trace["modules"][m]["seconds"] for m in MODULES
               if m in trace.get("modules", {}))
    toks = sum(r.get("prompt_tokens") or 0 for r in sources["log"]
               if r["ok"] and span[0] <= r["chunks"][0] <= span[1])
    if toks <= 0 or secs <= 0:
        return None
    return secs * 1e3 / (toks / 1e3)
