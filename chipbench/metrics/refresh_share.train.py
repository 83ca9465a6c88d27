"""Per cent of the window the host spent on index refreshes: the sum of
`RefreshEvent.seconds` (the time the host blocked on the rebuild plus its
dispatch) over the window."""


def read(rec):
    if "refresh_s" not in rec:
        return None
    t_open = rec["window"][0]
    return 100.0 * sum(rec["refresh_s"]) / (rec["steps"][-1][1] - t_open)
