"""Drive ``run.py`` with the timed path broken underneath (for the test):

    python faulty_run.py <fault> --workload ... --rehearse

``scores``: an answer altered where it is produced (every score of the search
shifted by 0.05).  ``half``: half of the rows left out of the scan (odd slots
masked), the top-k taken over the rest.  ``token``: a token altered where it
is produced (the decode engine picks the second-best logit).  ``lose``: a
step that leaves its state unchanged (every second file from passage 16 on is
never added to the index).  ``none``: the program as it is.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run  # noqa: E402


def plant(fault: str) -> None:
    if fault == "none":
        return
    import jax.numpy as jnp

    if fault == "lose":
        import re

        from pathway_tpu.stdlib.indexing import lowering

        sound_apply = lowering.ExternalIndexNode._apply_index_updates

        def forgetful(self, last, payloads, add_keys):
            def lost(key) -> bool:
                m = re.search(r"passage_(\d+)", repr(payloads.get(key)))
                return m is not None and int(m.group(1)) >= 16 and int(m.group(1)) % 2 == 1

            gone = [k for k in add_keys if lost(k)]
            for k in gone:
                last.pop(k)
            return sound_apply(self, last, payloads, [k for k in add_keys if k not in gone])

        lowering.ExternalIndexNode._apply_index_updates = forgetful
        return
    if fault == "token":
        from pathway_tpu.generation import engine

        def second_best(logits, seed, count, temperature):
            return jnp.argsort(logits)[-2].astype(jnp.int32)

        engine._pick_token = second_best
        return
    from pathway_tpu.ops import fused_serving

    sound = fused_serving.dense_fused_search

    def broken(q, vectors, valid, **kw):
        if fault == "half":
            valid = valid & (jnp.arange(valid.shape[0]) % 2 == 0)
        scores, idx = sound(q, vectors, valid, **kw)
        if fault == "scores":
            scores = scores + 0.05
        return scores, idx

    fused_serving.dense_fused_search = broken


if __name__ == "__main__":
    plant(sys.argv[1])
    sys.argv = [sys.argv[0]] + sys.argv[2:]
    code = run.main()
    sys.stdout.flush()
    os._exit(code)
