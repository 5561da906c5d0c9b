"""Autoscaler tests with the local-process NodeProvider.

Mirrors ray: FakeMultiNodeProvider-based autoscaler tests
(python/ray/tests/test_autoscaler_fake_multinode.py) — nodes are local
agent processes (SURVEY §4 "fakes" row).
"""
import time

import ray_tpu


def test_autoscaler_scales_up_and_down(ray_shared):
    from ray_tpu._private.worker import global_worker
    from ray_tpu.autoscaler import (AutoscalerConfig, LocalNodeProvider,
                                    StandardAutoscaler, request_resources)

    provider = LocalNodeProvider(global_worker().controller_addr)
    config = AutoscalerConfig(min_workers=0, max_workers=2,
                              idle_timeout_s=3.0, update_interval_s=0.5,
                              worker_node_config={"resources": {"CPU": 2}})
    scaler = StandardAutoscaler(provider, config)
    scaler.start()
    try:
        # Demand beyond the head node's 4 CPUs → scale up.
        request_resources(num_cpus=6)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if len(provider.non_terminated_nodes()) >= 1 and \
                    len([n for n in ray_tpu.nodes()
                         if n["state"] == "ALIVE"]) >= 2:
                break
            time.sleep(0.3)
        alive = [n for n in ray_tpu.nodes() if n["state"] == "ALIVE"]
        assert len(alive) >= 2, f"no scale-up: {alive}"
        assert ray_tpu.cluster_resources().get("CPU", 0) >= 6

        # Drop the demand floor → idle nodes terminate after the timeout.
        request_resources(num_cpus=0)
        deadline = time.monotonic() + 40
        while time.monotonic() < deadline:
            if not provider.non_terminated_nodes():
                break
            time.sleep(0.5)
        assert not provider.non_terminated_nodes(), "no scale-down"
    finally:
        scaler.stop()
        for pid in provider.non_terminated_nodes():
            provider.terminate_node(pid)
