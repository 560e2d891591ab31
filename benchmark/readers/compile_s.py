"""compile_s: seconds jax spent compiling (or reading its cache) in set-up."""


def read(run):
    return run.get("compile_s")
