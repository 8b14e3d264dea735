"""Times DPTrainer traced its step from the first call to the window's end
(`DPTrainer.step_traces`; each is a compile or a read from the compile
cache).  2 today: init_state's state is uncommitted, the first step's output
is committed to the mesh, so the second call is a new signature."""


def read(run):
    return run.step_traces
