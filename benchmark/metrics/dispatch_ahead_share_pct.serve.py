"""Serving engine: the share of the window's decode dispatches that were
queued on the device before the dispatch before them was read back.  From
stats()["dispatch_ahead"] (PR 29): `issued_ahead` over `decode_dispatches`.
Near 100 while every decode step has a successor (full slots, finishes by
length); every drain (a stop, a host op, a swap-out, a timeout, a failed
dispatch, an idle engine) and every step with no row left to go on costs
one.  A program without the counter gives nothing."""


def read(obs):
    ahead = obs["stats"].get("dispatch_ahead")
    if not ahead or not ahead["decode_dispatches"]:
        return None
    return 100 * ahead["issued_ahead"] / ahead["decode_dispatches"]
