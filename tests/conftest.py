"""Test harness: real pjit collectives on a virtual 8-device CPU mesh.

The reference's test strategy injects a mock-ray module (reference
tests/mock_ray.py:1-10, proxies.py:34-39) and never exercises the sync
protocol (SURVEY.md §4). Here the equivalent seam is strictly stronger:
XLA_FLAGS=--xla_force_host_platform_device_count=8 gives 8 real CPU devices,
so sharding/collective tests run the actual compiled SPMD programs.

The env is set BEFORE importing jax, so that the subprocesses the tests
start (CLI children, fleet workers, replicas) inherit the CPU platform too.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# CLI commands called in-process point jax at the checkout's persistent
# compile cache (devices.enable_compile_cache); the test process itself
# neither reads nor writes it, so no test depends on what an earlier run
# left there
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from spacy_ray_tpu.parallel.mesh import build_mesh

    return build_mesh(n_data=8)


@pytest.fixture(scope="session")
def tagger_config_text():
    return """
[paths]
train = null
dev = null

[nlp]
lang = "en"
pipeline = ["tok2vec","tagger"]

[components]

[components.tok2vec]
factory = "tok2vec"

[components.tok2vec.model]
@architectures = "spacy.HashEmbedCNN.v2"
width = 64
depth = 2
embed_size = 512

[components.tagger]
factory = "tagger"

[components.tagger.model]
@architectures = "spacy.Tagger.v2"

[components.tagger.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = ${components.tok2vec.model.width}

[corpora]

[corpora.train]
@readers = "spacy.JsonlCorpus.v1"
path = ${paths.train}

[corpora.dev]
@readers = "spacy.JsonlCorpus.v1"
path = ${paths.dev}

[training]
seed = 0
dropout = 0.1
accumulate_gradient = 1
patience = 0
max_epochs = 0
max_steps = 60
eval_frequency = 20

[training.optimizer]
@optimizers = "Adam.v1"
learn_rate = 0.01

[training.batcher]
@batchers = "spacy.batch_by_words.v1"
size = 600
tolerance = 0.2

[training.score_weights]
tag_acc = 1.0
"""
