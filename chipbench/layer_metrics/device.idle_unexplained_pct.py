"""Share of the traced window in which the device ran nothing and the
program's dry-time account names no cause: the trace's idle share
(``device.idle_pct``) less what the statistics' ``dry_no_request``,
``dry_window``, ``dry_late`` and ``dry_host`` entries booked in the window.

The account is a lower bound on what the chip felt, so what is left is not
under zero but for the window's edges (a step is booked when it ends): the
idle time after ``model.execute`` was called (``dispatch``), the read-back's
end (a step ahead counts as on the chip until its outputs are on the host),
and whatever a process-wide pause hid from the host's clock.  On a program
without the account every entry reads 0 and this is the idle share whole:
the program explains none of it.
"""

DRY = ("dry_no_request", "dry_window", "dry_late", "dry_host")


def read(ctx: dict):
    trace, delta = ctx.get("trace"), ctx.get("stats_delta")
    if not trace or not delta:
        return None
    explained_s = sum(delta.get(name + ".ns", 0) for name in DRY) / 1e9
    # written as ``device.idle_pct`` writes it, so that with nothing
    # explained the two read the same to the last bit
    idle_pct = 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    return idle_pct - 100.0 * explained_s / trace["window_s"]
