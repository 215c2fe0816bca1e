"""Wall of the process's first call through the entry point: compile, or a
read of the compiled program from the persistent cache, plus one run."""


def read(run, name):
    return run.get("first_call_s")
