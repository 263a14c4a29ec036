"""Layer: model step, training.  Over the experts a chip holds, the most
tokens an expert was routed in the window over the mean (the worst expert
layer's): 1 is a balanced router.  From the counters the expert layers
accumulate on the device (``PipelineModel.read_counters``), read once
before and once after the window."""


def read(record):
    if record.get("kind") != "train":
        return None
    return record.get("expert_load_max_over_mean")
