"""``kv_pages_used`` over ``kv_pages_total`` of the engine's ``stats()``,
mean of samples taken every quarter second through the window."""


def read(run):
    share = run.measures.get("kv_pages_used_share")
    return None if share is None else 100.0 * share
