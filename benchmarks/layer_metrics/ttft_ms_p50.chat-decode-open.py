"""Median time to first token (due time -> first streamed chunk with text)
in the open-loop chat-decode mix, from the generator's clock in the traced
run. Recorded, not judged."""


def read(sources):
    return sources["gen"].get("ttft_ms_p50")
