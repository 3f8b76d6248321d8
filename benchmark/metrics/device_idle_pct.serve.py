"""Share of the traced window in which no operation ran on the
device: 1 - union of device-operation intervals / window."""


def read(run):
    tr = run.get("trace")
    if not tr or tr.get("idle_share") is None:
        return None
    return 100.0 * tr["idle_share"]
