"""packed_group_share: live page groups whose `packed_mask` is set, over
live page groups, read from the pool after the window, in %."""


def read(run):
    if not run.packed["live_groups"]:
        return None
    return 100.0 * run.packed_share()
