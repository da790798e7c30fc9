"""Share (%) of the rows the device scored in the window that carry a
frame the skip detector left to score: the window's scored frames over
its scored chunks times the chunk's rows. A chunk that holds even one
scored frame is padded to the full chunk, so the rest is work the
detector saved on the host and the device did anyway. Program counters
(``IngestStats.refs`` and ``chunks``); None for a record that does not
carry the window's chunks."""


def read(record):
    run = record.get("run") or {}
    if not run.get("chunks"):
        return None
    return 100.0 * run["refs"] / (run["chunks"] * run["chunk"])
