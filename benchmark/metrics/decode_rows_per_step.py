"""Output tokens the clients received in the window over the decode steps
the engine counted in it (``serving_decode_steps_total``)."""


def read(run):
    m = run.measures
    if not m.get("decode_steps"):
        return None
    return m["out_tokens"] / m["decode_steps"]
