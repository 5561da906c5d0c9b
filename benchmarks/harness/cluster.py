"""Starting and ending the ray_tpu cluster a run drives."""
from __future__ import annotations

import glob
import os


def shutdown(node_ids: list[str]) -> int:
    """End the cluster this process started and everything below it;
    wait until each process has ended (the TPU runtime can take a while
    to let go of the chip), kill what outlives that, remove the nodes'
    shm segments.  Returns how many processes had to be killed."""
    import psutil

    import ray_tpu

    mine = psutil.Process().children(recursive=True)
    ray_tpu.shutdown()
    _, alive = psutil.wait_procs(mine, timeout=30.0)
    for p in alive:
        p.kill()
    psutil.wait_procs(alive, timeout=10.0)
    for n in node_ids:
        for f in glob.glob(f"/dev/shm/raytpu_{n[:16]}_*"):
            try:
                os.unlink(f)
            except OSError:
                pass
    return len(alive)
