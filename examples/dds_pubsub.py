"""Avionics-DDS example (paper Sec. 4.6): topics over subgroups, four QoS
levels, Spindle vs baseline.

A 16-node domain runs one publisher and 15 subscribers on a 10KB Sequence
topic at each QoS level — the paper's Fig. 18 scenario.

Run:  PYTHONPATH=src python examples/dds_pubsub.py
"""

from repro import use_compilation_cache
from repro.core import dds
from repro.core.group import RunReport


def bench(qos: dds.QoS, spindle: bool, samples: int = 400) -> RunReport:
    domain = dds.single_topic_domain(n_nodes=16, n_subscribers=15,
                                     qos=qos)
    g = domain.group(samples_per_publisher=samples, spindle=spindle)
    return g.run(backend="des")


def main():
    use_compilation_cache()
    print("DDS domain: 1 publisher, 15 subscribers, 10KB samples")
    print(f"{'QoS':<18} {'baseline GB/s':>14} {'spindle GB/s':>14} "
          f"{'speedup':>8}")
    for qos in dds.QoS:
        base = bench(qos, spindle=False, samples=150)
        spin = bench(qos, spindle=True)
        sp = spin.throughput_GBps / max(base.throughput_GBps, 1e-9)
        print(f"{qos.value:<18} {base.throughput_GBps:>14.2f} "
              f"{spin.throughput_GBps:>14.2f} {sp:>7.1f}x")

    # multi-topic domain: overlapping subgroups, one active topic
    print("\nmulti-topic domain (10 topics, one active):")
    domain = dds.Domain(n_nodes=16)
    for t in range(10):
        domain.create_topic(f"topic{t}", publishers=[t % 16],
                            subscribers=[n for n in range(16)
                                         if n != t % 16])
    g = domain.group(samples_per_publisher=0, spindle=True)
    # only topic0 publishes: an explicit Group-API send overrides the
    # scenario default for that subgroup
    g.subgroup(0).ordered_send(n=400)
    r = g.run(backend="des")
    print(f"  active-topic throughput with 9 idle topics: "
          f"{r.throughput_GBps:.2f} GB/s (adaptive batching keeps idle "
          f"subgroups nearly free)")


if __name__ == "__main__":
    main()
