"""aep_exposed_ms (ms per step, device trace): the device time of the AEP
``all-to-all`` ops during which no other op runs on that chip, per window
step, averaged over the cell's chips."""


def read(record):
    tr = record["trace"]
    steps = record["run"].steps
    if not tr or not steps or not tr["collective_ops"]:
        return None
    return 1e3 * tr["exposed_all_to_all_s"] / steps
