"""Pallas kernel launches on the device (custom calls with target
``tpu_custom_call``) per request answered in the traced window."""


def read(run):
    if run.trace is None or not run.trace["devices"] or not run.answered:
        return None
    return run.trace["pallas_launches"] / len(run.answered)
