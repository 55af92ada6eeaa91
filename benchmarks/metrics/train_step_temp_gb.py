"""The compiled train step's temporaries on a device as the executable's
``memory_analysis()`` counts them, in GB (1e9 bytes): the program's gauge
``trainer.step_temp_bytes``, set where the trainer compiled the step.  That
count holds a loop's carry twice, so it reads above what the buffer
assignment allocates (8.642 GB for 7.610 in the SmallThinker cell, PERF.md
PR 37; ``peak_bytes_reserved`` in the line's notes is the allocation): read
it as a step's size against its own earlier readings, not as the bytes
reserved.  It moves no rate by itself; it is the room that rematerialised
blocks buy ``train_samples_per_s`` back from (PERF.md section 3).  Nothing
where the program has no such gauge."""


def read(ctx):
    from mxnet_tpu.observability.registry import registry
    temp = registry().get("trainer.step_temp_bytes")
    if temp is None or not temp.value:
        return None
    return temp.value / 1e9
