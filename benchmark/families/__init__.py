"""What a family file provides: the contract between a model and the harness.

``benchmark/families/<family>.py`` (named by a configuration file's
``"family"``) is the ONLY place that knows a model's classes, the names of
its configuration keys, its module and kernel scopes and its counts of
operations and bytes. The shared harness — the traffic kinds, the scope
reducer, the readers, ``tools/rehearse_compile.py`` and the tests — reads no
model key itself: it asks the family. A later family is a new file here with
the members below, its plain reference under ``reference/`` and its
configuration files; ``tests/benchmark_checks/test_bm_manifest_rules.py``
holds every file in this directory to this list, but for ``HELPERS``:
``common.py`` is the training recipe the families share (``merged``,
``engine_config``, ``build_train(model, ...)``, ``lower_train_step(model,
...)``), which a family calls with its own model; no family imports another
family's private name.

Every family (``config`` is the configuration file as a dict; ``rehearse``
selects the tiny sizes the file carries under ``rehearse_cpu``):

    WIDTH_KEYS      the configuration keys that are widths (hidden,
                    intermediate, head counts and sizes, experts per token).
                    With every key ending ``_dim`` / ``_rank`` they may not be
                    listed in ``reduced`` and equal ``published[key]`` where
                    the file's ``published`` block carries the key
    KERNEL_TAGS     scope names of the step's Pallas kernels; a path element
                    that STARTS with a tag counts (``flash_bwd_dkv`` is
                    ``flash_bwd``), the first listed tag that matches wins.
                    ``scope_reduce`` gives each a ``kernel_ms`` row, which a
                    kernel's roofline reader divides its own count by
    MODULE_TAGS     module and scope names of the detail table's ``tag``
                    column, first match among a path's elements
    sizes(config, rehearse)
                    the sizes the family builds from, under the family's own
                    key names; every value is the file's when not rehearsing
    traffic_shapes(config, rehearse)
                    {"vocab_size": ids are drawn below it, "max_positions":
                    the longest sequence the model takes, "seq_scale": by how
                    much the rehearsal shrinks every length (1 on the chip)}:
                    all the traffic generator learns of a model
    build_train(config, global_batch, seed, devices, rehearse)
                    (engine, parameters) through the program's entry point,
                    weights made on the device from the seed
    reference_train(config, params, batch_ids, devices, rehearse)
                    (loss, gradient norm) of the plain reference
    judge_train(config, got_loss, got_gnorm, want_loss, want_gnorm)
                    (checks, detail): the comparison that decides ``correct``
    lower_train_step(config, traffic, devices)
                    the train step at real size as a ``jax.stages.Lowered``
                    over abstract state on ``devices`` (described chips, not
                    attached: no array exists); ``rehearse_compile`` compiles
                    it for memory, kernels and collectives
    train_flops_per_token(config, seq_len, rehearse)
    train_attention_flops_per_step(config, batch, seq_len, rehearse)
                    required operations, from ``roofline.py``'s counts

A family with a serving block has ALL of these, one without has none:

    build_serving(config, seed, rehearse, registry)     (engine, weights)
    check_serving(config, eng, params, prompts, rehearse, pad_to)
                    (checks, detail) against the reference's full forward
    lower_serving(config, traffic, device)
                    (facts to print, iterator of (program name, Lowered)):
                    every tick and prefill program over the configured pool
    decode_kv_bytes(config, contexts, rehearse)         bytes a step reads
"""

TRAINING = ("sizes", "traffic_shapes", "build_train", "reference_train",
            "judge_train", "lower_train_step", "train_flops_per_token",
            "train_attention_flops_per_step")
TAGS = ("WIDTH_KEYS", "KERNEL_TAGS", "MODULE_TAGS")
SERVING = ("build_serving", "check_serving", "lower_serving",
           "decode_kv_bytes")
TRAFFIC_SHAPES = ("vocab_size", "max_positions", "seq_scale")
HELPERS = ("common",)           # files of this directory that are no family
