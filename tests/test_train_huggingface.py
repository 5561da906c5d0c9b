"""HuggingFace Transformers integration: prepare_trainer + report callback.

Mirrors ray: python/ray/train/tests/test_transformers_trainer.py /
_transformers_utils.py behavior — a transformers.Trainer inside a
TorchTrainer worker group (gloo), fed by a ray_tpu Data shard, reporting
checkpoints + metrics through the train session.  Offline: the model is
a tiny nn.Module (no hub downloads).
"""
import os
import tempfile

import pytest


transformers = pytest.importorskip("transformers")


def _train_loop(config):
    import torch

    from ray_tpu.train import get_dataset_shard, get_context
    from ray_tpu.train.huggingface import (RayTrainReportCallback,
                                           prepare_trainer)

    class TinyRegressor(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.lin = torch.nn.Linear(1, 1)

        def forward(self, x=None, labels=None):
            logits = self.lin(x.float().unsqueeze(-1))
            out = {"logits": logits}
            if labels is not None:
                out["loss"] = torch.nn.functional.mse_loss(
                    logits, labels.float().unsqueeze(-1))
            return out

    rank = get_context().get_world_rank()
    out_dir = os.path.join(config["tmp"], f"rank{rank}")
    args = transformers.TrainingArguments(
        output_dir=out_dir,
        max_steps=4,
        per_device_train_batch_size=8,
        save_strategy="steps",
        save_steps=2,
        logging_steps=1,
        report_to=[],
        use_cpu=True,
        disable_tqdm=True,
    )
    trainer = transformers.Trainer(
        model=TinyRegressor(), args=args,
        train_dataset=get_dataset_shard("train"))
    trainer.add_callback(RayTrainReportCallback())
    trainer = prepare_trainer(trainer)
    trainer.train()


def test_transformers_trainer_reports_and_checkpoints(ray_shared, tmp_path):
    from ray_tpu import data
    from ray_tpu.train import ScalingConfig
    from ray_tpu.train.torch import TorchTrainer

    ds = data.range(64).map(
        lambda r: {"x": float(r["id"]), "labels": 2.0 * r["id"] + 1.0})
    trainer = TorchTrainer(
        _train_loop,
        train_loop_config={"tmp": str(tmp_path)},
        datasets={"train": ds},
        scaling_config=ScalingConfig(num_workers=2,
                                     resources_per_worker={"CPU": 1}))
    result = trainer.fit()
    assert result.error is None
    # logging_steps=1 puts a per-step loss into log_history; the callback
    # aggregates it into the report.
    assert "loss" in result.metrics
    # Rank 0 saved HF checkpoints; the newest rode the final report.
    assert result.checkpoint is not None
    ckpt_sub = os.path.join(result.checkpoint.path,
                            RayTrainReportCallbackName())
    assert os.path.isdir(ckpt_sub)
    # It is a real transformers checkpoint (model weights present).
    names = os.listdir(ckpt_sub)
    assert any(n.startswith(("model", "pytorch_model")) for n in names)
    # Ephemeral handoff consumed the callback's /tmp copies (no leak) and
    # stripped the marker from the stored copy.
    import glob

    assert glob.glob("/tmp/raytpu-hf-ckpt-*") == []
    from ray_tpu.train.checkpoint import Checkpoint

    assert not result.checkpoint.is_ephemeral()


def RayTrainReportCallbackName():
    from ray_tpu.train.huggingface import RayTrainReportCallback

    return RayTrainReportCallback.CHECKPOINT_NAME


def test_prepare_trainer_passthrough_for_torch_dataset(ray_shared):
    """A plain map-style torch dataset keeps the stock dataloaders."""
    import torch

    from ray_tpu.train.huggingface import prepare_trainer

    class TinyDs(torch.utils.data.Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            return {"x": torch.tensor([float(i)]),
                    "labels": torch.tensor([float(i)])}

    class TinyModel(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.lin = torch.nn.Linear(1, 1)

        def forward(self, x=None, labels=None):
            logits = self.lin(x)
            return {"loss": torch.nn.functional.mse_loss(logits, labels),
                    "logits": logits}

    with tempfile.TemporaryDirectory() as d:
        args = transformers.TrainingArguments(
            output_dir=d, max_steps=2, per_device_train_batch_size=4,
            save_strategy="no", report_to=[], use_cpu=True,
            disable_tqdm=True)
        trainer = transformers.Trainer(model=TinyModel(), args=args,
                                       train_dataset=TinyDs())
        trainer = prepare_trainer(trainer)
        loader = trainer.get_train_dataloader()
        batch = next(iter(loader))
        assert batch["x"].shape[0] == 4
