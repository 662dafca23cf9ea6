"""Kernels: the live blocks the decode attention kernel takes a grid step
over the window's decode dispatches.  From stats()["decode_kernel"] (PR 35;
`kernel` and `mla_decode` since PR 36): `live_blocks` over `steps`, both
counted on the host from each dispatch's contexts as the kernel lays its
work list out (a row's blocks under its context; an idle row one block and
one step).  The kernel takes up to `fan` consecutive blocks of a row a step
under one softmax, so the reading lies between 1 and `fan`: the nearer it is
to `fan`, the fewer steps a launch pays its chain for.  The kernel, its fan,
the blocks, steps and launches go to the log.  A program without the
counter, an engine whose decode runs no such kernel (a description with no
pool, the XLA gather path, a host without the chip) or a window with no
decode step gives nothing."""


def read(obs):
    dk = obs["stats"].get("decode_kernel")
    if not dk or dk.get("kernel") is None or not dk["steps"]:
        return None
    obs["log"](f"[decode kernel] {dk['kernel']} fan {dk['fan']}: "
               f"{dk['live_blocks']} live blocks in {dk['steps']} steps, "
               f"{dk['launches']} launches")
    return dk["live_blocks"] / dk["steps"]
