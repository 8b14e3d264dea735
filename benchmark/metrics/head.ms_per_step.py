"""Device time of the masked-LM head per step, in ms: the tied decoder, the
softmax over the vocabulary and the cross-entropy, forward and backward
(class `head` of op_classes/07-head.json: an operation that touches an array
whose last dimension is the vocabulary).  Part of model.xla_ms_per_step.
None where no trace was read or no such operation ran."""


def read(run):
    if not run.trace:
        return None
    return run.trace.class_ms_per_step("head")
