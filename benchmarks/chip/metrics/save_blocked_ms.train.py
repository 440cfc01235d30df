"""Mean time a save held the training loop: ``last_save_stats
["blocked_s"]`` of each save dispatched in the window."""


def read(run):
    blocked = run.read.get("save_blocked_s")
    if not blocked:
        return None
    return 1e3 * sum(blocked) / len(blocked)
