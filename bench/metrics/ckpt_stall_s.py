"""Host seconds the train loop stalled in CheckpointedTrainer.checkpoint_now
per save of the window: phase 1 and the fork of the persist child."""


def read(ctx):
    stalls = ctx["stalls_s"]
    return sum(stalls) / len(stalls) if stalls else None
