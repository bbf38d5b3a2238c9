"""Share of the window's replies that took a second or more on the client's
clock: connection attempts the listening socket dropped (the stdlib server's
backlog of 5), sent again by the client's TCP after one second."""


def read(run):
    if "slow_reply_share" not in run.records:
        return None
    return 100.0 * run.records["slow_reply_share"]
