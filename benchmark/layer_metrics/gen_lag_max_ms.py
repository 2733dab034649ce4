"""Entry points: how late the load generator sent (sent - due), the worst of
the window, ms. A starved generator must not read as a fast server."""


def read(r):
    lag = [1000.0 * (x["sent"] - x["due"]) for x in r.win.records
           if x["sent"] is not None]
    return max(lag) if lag else None
