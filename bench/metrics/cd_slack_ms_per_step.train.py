"""Slack the governor measured per rank per step (``GovernorReport.
total_slack``, host clock, over the window's steps), in ms."""


def read(run):
    lay = run["layer"]
    if "slack_s" not in lay or not lay["governed_steps"]:
        return None
    return 1e3 * lay["slack_s"] / lay["governed_steps"]
