"""Output tokens that came out inside the window, per second of window:
recorded, not judged. Under the closed loop the work is fixed, so the count
takes one of a few values (9 of 12 runs read the same number to the last
digit and 3 read 1.1–1.5% less): no bound between 1% and eight times a
spread of zero fits it, and ``tpot_p50_ms`` carries the same information
without the steps. Below the knee of an open loop it follows the offered
load."""


def read(facts):
    if "out_tokens" not in facts:
        return None
    return facts["out_tokens"] / facts["window_s"]
