"""TorchTrainer: gloo process group across train-worker actors.

Mirrors ray: python/ray/train/tests/test_torch_trainer.py (CPU/gloo
configuration — the reference's tests run the same way on laptop CI).
"""
import numpy as np


def test_torch_trainer_ddp_gloo(ray_shared):
    from ray_tpu.train import ScalingConfig
    from ray_tpu.train.torch import TorchTrainer

    def train_loop(config):
        import torch
        import torch.distributed as dist

        from ray_tpu.train import report
        from ray_tpu.train.torch import prepare_model

        assert dist.is_initialized() and dist.get_world_size() == 2
        torch.manual_seed(0)
        model = torch.nn.Linear(4, 1)
        model = prepare_model(model)
        opt = torch.optim.SGD(model.parameters(), lr=0.1)
        x = torch.randn(64, 4)
        y = x.sum(dim=1, keepdim=True)
        loss = None
        for _ in range(20):
            opt.zero_grad()
            loss = torch.nn.functional.mse_loss(model(x), y)
            loss.backward()      # DDP allreduces grads over gloo
            opt.step()
        # Ranks must agree on the (allreduce-synced) weights.
        w = model.module.weight if hasattr(model, "module") \
            else model.weight
        report({"loss": float(loss), "w0": float(w.flatten()[0])})

    trainer = TorchTrainer(
        train_loop,
        scaling_config=ScalingConfig(num_workers=2,
                                     resources_per_worker={"CPU": 1}))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["loss"] < 1.0
