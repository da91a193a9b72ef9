"""TorchTrainer — torch-DDP training over the actor gang.

Reference analog: ray.train.torch (TorchTrainer + TorchConfig,
python/ray/train/torch/config.py:36,66,115): the framework supplies
ranks and a rendezvous address, `dist.init_process_group` builds the
collective group, and ``prepare_model``/``prepare_data_loader`` wrap
the user's model/loader for DDP. Here the process group runs gloo
(CPU) — on TPU fleets the JaxTrainer is the native path; TorchTrainer
exists so torch workloads (and users migrating from the reference)
run unchanged on CPU nodes of the same cluster.
"""

from __future__ import annotations

from dataclasses import dataclass

from ray_tpu.train.config import BackendConfig
from ray_tpu.train.session import Checkpoint
from ray_tpu.train.trainer import JaxTrainer


class TorchTrainer(JaxTrainer):
    """Same orchestration as JaxTrainer (WorkerGroup gang, session
    reporting, checkpoint recovery); the backend hook initializes a
    torch.distributed gloo process group on every worker — including
    single-worker runs, so user loops can use dist.* unconditionally
    (reference TorchConfig semantics)."""

    _backend_setup = "setup_torch_distributed"
    _setup_single_worker = True
    _opens_jax_backend = False

    def __init__(self, *args, torch_config=None, **kwargs):
        if torch_config is not None and not isinstance(torch_config,
                                                       TorchConfig):
            # normalize duck-typed configs so TorchConfig is the ONE
            # place the gloo constraint lives
            torch_config = TorchConfig(
                backend=getattr(torch_config, "backend", "gloo"),
                timeout_s=getattr(torch_config, "timeout_s", 1800))
        super().__init__(*args, **kwargs)
        self.torch_config = torch_config
        if torch_config is not None:
            # forwarded to setup_torch_distributed via the rendezvous
            # payload (init_process_group timeout)
            self._backend_setup_extra = {
                "timeout_s": torch_config.timeout_s}


def prepare_model(model):
    """Wrap a torch model for the current world: DDP when world > 1
    (reference: train.torch.prepare_model)."""
    import torch.distributed as dist
    if dist.is_initialized() and dist.get_world_size() > 1:
        from torch.nn.parallel import DistributedDataParallel
        return DistributedDataParallel(model)
    return model


class _EpochDataLoader:
    """Wraps a DDP DataLoader so each ``__iter__`` advances the
    DistributedSampler epoch — without set_epoch every epoch would
    replay the identical shuffle order (reference:
    prepare_data_loader's epoch plumbing)."""

    def __init__(self, loader, sampler):
        self._loader = loader
        self.sampler = sampler
        self._epoch = -1

    def __iter__(self):
        self._epoch += 1
        self.sampler.set_epoch(self._epoch)
        return iter(self._loader)

    def __len__(self):
        return len(self._loader)

    def __getattr__(self, name):
        return getattr(self._loader, name)


def prepare_data_loader(loader):
    """Re-build a DataLoader with a DistributedSampler sharding by
    rank (reference: train.torch.prepare_data_loader). The original
    loader's shuffle intent (RandomSampler vs sequential) is
    preserved; pin_memory / collate / workers carry over; iteration
    advances the sampler epoch so shuffles differ per epoch."""
    import torch.distributed as dist
    if not dist.is_initialized() or dist.get_world_size() == 1:
        return loader
    from torch.utils.data import DataLoader, RandomSampler
    from torch.utils.data.distributed import DistributedSampler
    shuffle = isinstance(getattr(loader, "sampler", None),
                         RandomSampler)
    sampler = DistributedSampler(
        loader.dataset, num_replicas=dist.get_world_size(),
        rank=dist.get_rank(), shuffle=shuffle)
    new_loader = DataLoader(
        loader.dataset, batch_size=loader.batch_size,
        sampler=sampler, num_workers=loader.num_workers,
        collate_fn=loader.collate_fn, drop_last=loader.drop_last,
        pin_memory=loader.pin_memory)
    return _EpochDataLoader(new_loader, sampler)


@dataclass
class TorchConfig(BackendConfig):
    """(reference: ray.train.torch.TorchConfig) ``backend`` must be
    gloo here — this image has no CUDA, so nccl cannot initialize;
    the error names the constraint instead of failing inside
    torch.distributed."""

    backend: str = "gloo"
    timeout_s: int = 1800

    def __post_init__(self):
        if self.backend != "gloo":
            raise ValueError(
                f"TorchConfig.backend={self.backend!r}: only gloo is "
                f"available (CPU-only torch in this image; TPU "
                f"training is the JaxTrainer's job)")


def get_device():
    """(reference: train.torch.get_device) The device assigned to
    this worker — CPU in this torch build (TPU compute goes through
    jax, not torch)."""
    import torch
    return torch.device("cpu")


def get_devices() -> list:
    """(reference: train.torch.get_devices)"""
    return [get_device()]


def prepare_optimizer(optimizer):
    """(reference: train.torch.prepare_optimizer — wraps for AMP;
    identity here, where CPU gloo training has no AMP scaler)."""
    return optimizer


def backward(tensor) -> None:
    """(reference: train.torch.backward — scales under AMP; plain
    backward here)."""
    tensor.backward()


def enable_reproducibility(seed: int = 0) -> None:
    """Seed torch/numpy/python and force deterministic algorithms
    (reference: train.torch.enable_reproducibility)."""
    import os
    import random

    import numpy as np
    import torch
    torch.manual_seed(seed)
    random.seed(seed)
    np.random.seed(seed)
    torch.use_deterministic_algorithms(True, warn_only=True)
    os.environ.setdefault("PYTHONHASHSEED", str(seed))


class TorchCheckpoint(Checkpoint):
    """Model-state checkpoint (reference:
    ray.train.torch.TorchCheckpoint): ``from_model`` writes a
    state_dict into a directory and returns a TorchCheckpoint, so the
    reference idiom ``ckpt.get_model(model)`` works. The caller owns
    the directory (``report(checkpoint=...)`` persists a COPY into the
    trial dir — delete the local one after reporting in checkpoint-
    per-epoch loops, or pass a ``directory=`` you manage)."""

    FILE = "model_state.pt"

    @classmethod
    def from_model(cls, model, directory: str | None = None
                   ) -> "TorchCheckpoint":
        import os
        import tempfile

        import torch
        directory = directory or tempfile.mkdtemp(
            prefix="torch_ckpt_")
        os.makedirs(directory, exist_ok=True)
        state = model.state_dict() if hasattr(model, "state_dict") \
            else model
        torch.save(state, os.path.join(directory, cls.FILE))
        return cls(directory)

    def get_model(self, model):
        """Load the stored state_dict into ``model`` (returned)."""
        import os

        import torch
        state = torch.load(
            os.path.join(self.path, TorchCheckpoint.FILE),
            weights_only=True)
        model.load_state_dict(state)
        return model
