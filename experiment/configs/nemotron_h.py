"""Launch config of the Nemotron-H family (hybrid Mamba-2 / experts /
attention): causal-LM training through the same launcher as BERT.

    python experiment/launch.py -c experiment/configs/nemotron_h.py

The model's keys come from a JSON file under the published names
(``SKYTPU_NEMOTRON_JSON``; ``benchmarks/configs/nemotron-3-nano-30b-a3b
.json`` is one); without it the family's defaults stand, which are the
published 52-layer model and fit no single chip.  A file that states a
chip's share says so with ``n_routed_experts_published``: then
``n_routed_experts`` counts the experts HELD (a range starting at
``experts_held_start``) and the router keeps its published width.

Other knobs, as in ``experiment/config.py``: ``SKYTPU_CORE_NUM``,
``SKYTPU_MICROBATCHES``, ``SKYTPU_BATCH_SIZE``, ``SKYTPU_SEQ_LEN``,
``SKYTPU_ALLOCATE_TYPE``, ``SKYTPU_SCHEDULE``, ``SKYTPU_OPTIM``,
``SKYTPU_LR``, ``SKYTPU_MAX_ITERS``, ``SKYTPU_MAX_EPOCHS``,
``SKYTPU_LOG_ROOT``; ``SKYTPU_DATA_SEED`` seeds the token ids.

Layers of three kinds and unequal cost: the profile times each kind's own
stage programs (``timed="programs"``), and the engine, seeing layers of
hundreds of megabytes, runs one program a layer.
"""

import json
import os
import os.path as osp

from skycomputing_tpu.models.nemotron_h import (
    NemotronHConfig,
    nemotron_h_layer_configs,
)

ALLOCATE_TYPE = os.getenv("SKYTPU_ALLOCATE_TYPE", "optimal")
CORE_NUM = int(os.getenv("SKYTPU_CORE_NUM", "4"))
BATCH_SIZE = int(os.getenv("SKYTPU_BATCH_SIZE", "4"))
MAX_SEQ_LENGTH = int(os.getenv("SKYTPU_SEQ_LEN", "4096"))
NUM_MICROBATCHES = int(os.getenv("SKYTPU_MICROBATCHES", "4"))
SCHEDULE = os.getenv("SKYTPU_SCHEDULE", "gpipe")

__keys = {}
if os.getenv("SKYTPU_NEMOTRON_JSON"):
    with open(os.environ["SKYTPU_NEMOTRON_JSON"]) as __fh:
        __keys = json.load(__fh)
__model = NemotronHConfig.from_dict(__keys)
model_config = nemotron_h_layer_configs(__model)

__LOG_ROOT = osp.join(
    os.getenv("SKYTPU_LOG_ROOT", "logs"),
    f"nemotron_h_{CORE_NUM}nodes_{__model.num_hidden_layers}layers",
    ALLOCATE_TYPE,
)
logging_config = dict(filename=osp.join(__LOG_ROOT, "allocation.log"))

worker_config = [
    dict(
        name=f"tpu-{i}",
        device_config=dict(device_index=i),
        extra_config=dict(slowdown=1.0, mem_limit=-1),
    )
    for i in range(CORE_NUM)
]

# token ids uniform over the vocabulary (slice) held: a row is one n-gram
# as long as the sequence, so nothing repeats
__dataset = dict(
    type="RandomLmDataset",
    num_samples=16 * BATCH_SIZE,
    seq_length=MAX_SEQ_LENGTH,
    vocab_size=__model.vocab_size,
    ngram=MAX_SEQ_LENGTH,
    seed=int(os.getenv("SKYTPU_DATA_SEED", "0")),
)
data_config = dict(
    dataset_cfg=__dataset,
    dataloader_cfg=dict(batch_size=BATCH_SIZE, shuffle=True),
)

allocator_config = dict(
    type=ALLOCATE_TYPE,
    benchmark_config=dict(
        model=dict(
            # weights + gradients + Adam's two moments, in float32
            param_scale=4,
            # measured seconds, not XLA's FLOP count (a scan and a matrix
            # product of equal FLOPs are not equally long): a layer is
            # profiled by timing the programs a one-layer stage runs, which
            # are the engine's own where it runs a program a layer
            timed="programs",
            # a layer is profiled on what a stage program sees: one
            # microbatch
            data_generator_cfg=dict(
                generator_type="DataloaderGenerator",
                generator_cfg=dict(generator_cfg=dict(
                    dataset_cfg=dict(__dataset, num_samples=BATCH_SIZE),
                    dataloader_cfg=dict(
                        batch_size=max(BATCH_SIZE // NUM_MICROBATCHES, 1),
                        shuffle=False),
                )),
            ),
        ),
        device=dict(
            model_config=[
                dict(layer_type="MatmulStack", features=1024, depth=4)
            ],
            iterations=10,
            data_generator_cfg=dict(
                generator_type="RandomTensorGenerator",
                generator_cfg=dict(size=(256, 1024)),
            ),
        ),
    ),
)

train_config = dict(
    optim_cfg=dict(
        optim_type=os.getenv("SKYTPU_OPTIM", "adamw"),
        learning_rate=float(os.getenv("SKYTPU_LR", "0.0001")),
    ),
    loss_cfg=dict(type="CausalLmLoss"),
    runner_cfg=dict(
        max_epochs=int(os.getenv("SKYTPU_MAX_EPOCHS", "1")),
        max_iters=int(os.getenv("SKYTPU_MAX_ITERS", "30")),
    ),
    hook_config=[
        dict(type="StopHook", root=__LOG_ROOT),
        dict(type="DistributedTimerHelperHook"),
    ],
    timer_config=dict(root=__LOG_ROOT),
)
