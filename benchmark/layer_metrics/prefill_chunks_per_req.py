"""Prefill chunks ingested in the window per request that got its first
token in it (the proxy's count of ``prefill_tick`` calls)."""


def read(facts):
    if not facts.get("first_tokens"):
        return None
    return facts["prefill_chunks"] / facts["first_tokens"]
