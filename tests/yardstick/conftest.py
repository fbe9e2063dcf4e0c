"""The benchmark's entries set process-wide program flags
(``entries/common.program_flags``) as a run of the benchmark should; in
tier-1 a worker process goes on to other test files, which must find the
flags as they were."""

import dataclasses

import pytest


@pytest.fixture(autouse=True)
def program_flags_restored():
    from paddlebox_tpu.config import FLAGS
    saved = dataclasses.asdict(FLAGS)
    yield
    for name, value in saved.items():
        setattr(FLAGS, name, value)
