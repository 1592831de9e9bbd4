"""One module per model family: what the harness cannot know from sizes.

A configuration file names its family (``"family": "gpt2"``) and the
harness finds ``benchmark/families/<family>.py`` by that name, so a
configuration of a new architecture is a new file here, a new file under
``configs/`` and entries in ``BENCHMARK.json``: nothing that exists is
edited. Every function takes the configuration file's whole dict, so a
family reads its sizes wherever its file keeps them. A family module has:

``vocab_size(cfg)``
    token ids are drawn below it;
``build_model(cfg, kind)``
    the program's model object for a ``"train"`` or ``"serve"`` system
    (anything ``deepspeed_tpu.initialize`` / ``init_inference`` takes: it
    has ``init_params(key)`` and ``loss(params, batch)``);
``reference_logits(params, ids, cfg)``, ``reference_loss(params, ids, cfg)``
    the plain reference, on the parameter values the system holds;
``train_flops_per_token(cfg, seq)``, ``decode_flops_per_token(cfg)``,
``decode_bytes_per_token(cfg, context)``
    operations and bytes from shapes, for MFU and the decode roofline;
and, where the family runs the flash kernels,
``flash_flops_per_sequence(cfg, seq)``, ``flash_bytes_per_sequence(cfg,
seq)``. A metric reader whose family lacks its function returns nothing.
"""

import importlib

from benchmark import manifest as mf


def get(name):
    if not mf.NAME_RE.match(str(name)) or \
            not (mf.BENCH_DIR / "families" / f"{name}.py").exists():
        have = sorted(p.stem for p in (mf.BENCH_DIR / "families").glob("*.py")
                      if p.stem != "__init__")
        raise SystemExit(f"benchmark: no model family {name!r} under "
                         f"benchmark/families/ (have: {have})")
    return importlib.import_module(f"benchmark.families.{name}")
