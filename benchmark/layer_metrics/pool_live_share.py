"""Share of the KV pool's tokens that live sequences' visible context
holds, averaged over the window's decode steps (the proxy's count of
prompt and emitted tokens per live sequence). ``pool_pages_used_peak``
also counts the pages that the prefix registry keeps of finished prompts;
this is what the traffic itself keeps in use."""


def read(facts):
    steps, tokens = facts.get("decode_steps"), facts.get("pool_tokens")
    if not steps or not tokens:
        return None
    return 100.0 * sum(s[2] for s in steps) / len(steps) / tokens
