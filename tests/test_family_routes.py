"""The route harness (``tests/family_routes.py``) and the compiler rule
(``tests/conftest.py``) are themselves held to something: the padded
reference reads what the unpadded one gives, and each kind of test module
compiles as the rule says."""

import os

import jax
import numpy as np
import pytest

from family_routes import FAMILIES, REF_LEN, prompts_of, ref_logits
from tpuserve.models.config import get_model_config
from tpuserve.models.weights import init_params


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_the_padded_reference_reads_the_unpadded_rows(name):
    """``ref_logits`` runs the reference over the sequence padded to
    ``REF_LEN`` and reads the real positions' rows: at the first, a middle
    and the last position of a sequence that is not a multiple of any
    chunk, they are the rows of the reference on the sequence alone, to a
    FIFTH of the tolerance the family's route tests allow.  A row past the
    real sequence (the padded pass's last, say) is far off, and
    ``ref_logits`` refuses to read one."""
    family = FAMILIES[name]
    cfg = get_model_config(family.model)
    params = init_params(cfg, seed=3)
    (seq,) = prompts_of(37, seed=12)
    at = [0, 18, 36]
    alone = np.asarray(family.ref.logits_at(
        params, cfg, np.asarray([seq], np.int32), [(0, p) for p in at]))
    padded = ref_logits(family, params, cfg, seq, at)
    np.testing.assert_allclose(padded, alone, atol=family.atol / 5)
    # the pin can fail: the row after the sequence's last is another row
    tokens = np.zeros((1, REF_LEN), np.int32)
    tokens[0, :len(seq)] = seq
    past = np.asarray(family.ref.logits_at(params, cfg, tokens,
                                           [(0, len(seq))]))[0]
    assert np.max(np.abs(past - alone[-1])) > 100 * family.atol
    with pytest.raises(AssertionError):
        ref_logits(family, params, cfg, seq, [len(seq)])


@pytest.mark.parametrize("path,skips", [
    ("tests/benchmark/test_benchmark_rehearsal.py", False),
    ("tests/test_chip_compile_cells.py", False),
    ("tests/test_falcon_h1.py", True),
])
def test_which_modules_compile_without_xlas_expensive_passes(path, skips,
                                                             request):
    """The rule of ``tests/conftest.py``, one function of a module's path:
    the benchmark's own tests (by directory) and the chip-compile files
    keep the whole optimiser, a family file does without; and this
    module is under the flag as the rule says, in this process alone."""
    # (pytest registers a conftest.py under its path; ``import conftest``
    # finds whichever directory's was imported last)
    rule = request.config.pluginmanager.get_plugin(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "conftest.py")).skips_xla_optimizations
    assert rule(path) is skips
    assert rule(os.path.join("/somewhere/else", path)) is skips
    assert jax.config.read("jax_disable_most_optimizations") \
        is rule(__file__) is True
    assert "JAX_DISABLE_MOST_OPTIMIZATIONS" not in os.environ
