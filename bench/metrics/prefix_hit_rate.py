"""Prefix cache: share of the prompt positions offered over the window that
came from cached pages and were never prefilled (``100 x
EngineStats.prefix_tokens_reused / (prefix_tokens_reused +
prompt_tokens_ingested)``), in percent."""


def read(ctx):
    reused = ctx.stats.get("prefix_tokens_reused")
    fed = ctx.stats.get("prompt_tokens_ingested")
    if reused is None or fed is None or not reused + fed:
        return None
    return 100.0 * reused / (reused + fed)
