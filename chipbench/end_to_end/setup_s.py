"""Set-up: from the start of the run to the end of the warm-up calls
(imports, device check, building the deployment and its first state,
loading or compiling every program the window runs)."""


def read(run):
    return run.setup_s
