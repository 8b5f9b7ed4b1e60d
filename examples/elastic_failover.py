"""Elastic training with failures: virtual-synchrony view changes,
straggler null-rounds, and restart from the checkpoint watermark.

A 16-worker data-parallel job loses two nodes mid-run, absorbs a straggler
with null-rounds, admits a replacement, and never stalls.

Run:  PYTHONPATH=src python examples/elastic_failover.py
"""

from repro import use_compilation_cache
from repro.train.elastic import ElasticConfig, ElasticRuntime


def main():
    use_compilation_cache()
    rt = ElasticRuntime(members=list(range(16)),
                        cfg=ElasticConfig(heartbeat_timeout=3,
                                          checkpoint_every=10))
    events = {15: ("fail", 3), 25: ("fail", 7), 30: ("straggle", 11),
              40: ("join", 16)}
    for r in range(60):
        if r in events:
            kind, node = events[r]
            if kind == "fail":
                print(f"  !! node {node} fails at round {r}")
                rt.fail(node)
            elif kind == "straggle":
                print(f"  ~~ node {node} straggles for 4 rounds")
                rt.delay(node, 4)
            elif kind == "join":
                print(f"  ++ node {node} requests to join")
                rt.join(node)
        info = rt.step()
        if info["view_change"] is not None:
            print(f"round {info['round']:3d}: VIEW CHANGE -> view "
                  f"{info['view_change']} members="
                  f"{list(rt.view.members)} "
                  f"restart watermark={rt.restart_watermark()}")
        elif info["null_rounds"]:
            print(f"round {info['round']:3d}: null-rounds for "
                  f"{info['null_rounds']} (dp={info['dp_size']}, "
                  f"{len(info['contributed'])} contributed)")
    print(f"\nfinal view: {rt.view.vid} with {len(rt.view.members)} "
          f"members after {len(rt.view_changes)} view changes")
    assert 16 in rt.view.members and 3 not in rt.view.members
    print("training never stalled: every round either contributed or "
          "null-rounded — the Sec. 3.3 guarantee, at the training layer.")


if __name__ == "__main__":
    main()
