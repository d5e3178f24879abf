"""Host milliseconds to issue one step (the calls, not the wait): median
over the window's untraced steps. Host clock."""
import statistics


def read(run):
    if run["dispatch_s"]:
        return 1e3 * statistics.median(run["dispatch_s"])
