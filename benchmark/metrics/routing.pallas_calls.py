"""`tpu_custom_call`s in the compiled step's HLO: which route compiled.  On
the fused-ring preset 2 at dp=1 (codec encode and decode) and, for the
10x2048^2 MLP at dp=4, 9 (one reduce-scatter+update, eight gathers)."""


def read(run):
    return run.check["pallas_calls"]
