"""Compilations counted by the program's CompileWatcher (``jax_compile_total``)
after the window less before it. Should be 0: everything is warmed up."""


def read(run):
    return run.measures.get("compiles_in_window")
