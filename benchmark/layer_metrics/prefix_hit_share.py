"""Layer: serving/kv_cache.py (PrefixCache). Prompt tokens served from
cached prefix pages over all prompt tokens of the drain (`prefix_stats`,
exact counts).
"""

def compute(record):
    prefix = record["prefix"]
    if not prefix or not record["prompt_tokens"]:
        return None
    return 100.0 * prefix["tokens_reused"] / record["prompt_tokens"]
