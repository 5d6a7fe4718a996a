"""Device: of the idle seconds between programs, the share to which
perfbench/host_timeline.py gave a name other than `unattributed`, in
percent."""

from perfbench import host_timeline


def read(run):
    found = host_timeline.of_run(run)
    if not found or not found["idle_s"]["between"]:
        return None
    return 100.0 * found["idle_s"]["named"] / found["idle_s"]["between"]
