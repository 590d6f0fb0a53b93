"""Layer: serving/kv_cache.py. Peak pages in use over the pool's pages
(`paged_stats`, exact counts): how close admission runs to the pool.
"""

def compute(record):
    paged = record["paged"]
    if not paged:
        return None
    return 100.0 * paged["pages_in_use_peak"] / paged["num_pages"]
