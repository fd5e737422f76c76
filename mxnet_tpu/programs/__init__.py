"""mxnet_tpu.programs — one registry for every compiled program.

The compiled-program plumbing that used to be hand-threaded three
separate times (``CompiledTrainStep`` / ``CompiledEvalStep`` /
``DecodePredictor``) lives here once (docs/programs.md):

* :mod:`~mxnet_tpu.programs.spec` — :class:`ProgramSpec` (name,
  abstract args, donation map, partition rules, trace counters ->
  artifact / fingerprint) and the shared ``_probing``
  guard helpers;
* :mod:`~mxnet_tpu.programs.partition` — regex partition rules over
  named param trees (the fmengine ``match_partition_rules`` idiom);
* :mod:`~mxnet_tpu.programs.aot` — AOT-serialized executables in a
  content-addressed on-disk cache (``MXNET_AOT`` /
  ``MXNET_PROGRAM_CACHE``), so fleet hosts cold-start by
  DESERIALIZING their serving programs instead of retracing them;
* :mod:`~mxnet_tpu.programs.registry` — the live-spec registry plus
  the canonical catalog ``tools/mxlint.py`` enumerates.
"""
from . import aot, partition, registry
from .aot import AOT_STATS, AotDispatch
from .partition import build_shardings, match_partition_rules, \
    rules_from_plan
from .registry import REGISTRY, ProgramRegistry
from .spec import ProgramSpec, probe_artifact, probe_lowered_text, \
    probing

__all__ = ["AOT_STATS", "AotDispatch", "ProgramRegistry", "ProgramSpec",
           "REGISTRY", "aot", "build_shardings", "match_partition_rules",
           "partition", "probe_artifact", "probe_lowered_text", "probing",
           "registry", "rules_from_plan"]
