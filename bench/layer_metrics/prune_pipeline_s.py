"""Seconds of ``MosaicPipeline.run`` (``core/pipeline.py``) on the host
clock, ended by ``block_until_ready`` on the artifact's parameters."""


def read(ctx):
    return ctx["prune_s"]
