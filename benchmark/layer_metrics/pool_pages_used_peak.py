"""Most pages of the KV pool in use at any decode step of the window, as a
share of the pool (``KVPagePool.used_pages`` read at every step: live
sequences' pages and those the prefix registry keeps of finished prompts)."""


def read(facts):
    if not facts.get("pool_pages"):
        return None
    return 100.0 * facts["pool_pages_used_peak"] / facts["pool_pages"]
