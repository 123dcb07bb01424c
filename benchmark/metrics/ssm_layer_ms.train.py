"""Device ms a step of the Mamba-2 layers, both passes: the scopes
``ssm.proj`` (both projections), ``ssm.conv``, ``ssm.scan`` and
``ssm.norm``, as ``moe_layer_ms.train`` reads the expert layers.  Adam's
update is in phase ``opt`` and in no scope.  From the program's recorder
(``profiler.program_ops``) through benchmark/scopes.py; silent where the step
has none of these scopes (a model without Mamba-2 layers) or no table."""
from benchmark import scopes

SCOPES = ("ssm.proj", "ssm.conv", "ssm.scan", "ssm.norm")


def read(run):
    return scopes.scopes_ms(run, SCOPES)
