"""Serving engine: stats()["prefill_dispatches"] over the requests that
became due in the window — how many packed prefill programs one request
costs (chunking raises it, packing lowers it)."""


def read(obs):
    if not obs["admitted_in_window"]:
        return None
    return obs["stats"]["prefill_dispatches"] / obs["admitted_in_window"]
