"""Token ids the fit loop took in the window (``train_tokens_total``, which
counts every integer-fed batch beside ``fit_steps_total``) over the window's
seconds."""


def read(run):
    m = run.measures
    if not m.get("tokens"):
        return None
    return m["tokens"] / m["window_s"]
