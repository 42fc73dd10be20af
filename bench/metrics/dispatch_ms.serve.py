"""Device time of one run of the serve pipeline's executable (ms).

From the ``XLA Modules`` events of the traced window: the executable with
the most device time in the window is the engine's batched pipeline (one
run for each dispatched batch), and this is its device time divided by its
runs.  It leaves out the host's part of a dispatch (padding, transfer,
copy back and validation), which no span of the program separates from
the device's without switching the pipeline to another path.
"""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("modules"):
        return None
    _name, runs, seconds = trace["modules"][0]
    return 1e3 * seconds / runs
