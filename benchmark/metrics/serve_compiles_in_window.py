"""``jax_compile_total`` and the engine's ``stats()["compiles"]`` after the
window less before it. Should be 0."""


def read(run):
    return run.measures.get("compiles_in_window")
