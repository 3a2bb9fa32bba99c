# NOTE: no XLA_FLAGS here — smoke tests and benches must see 1 device.
# Multi-device integration tests spawn subprocesses (tests/test_multidevice.py).
import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")


class IntakeNode:
    """Builds one kind of aggregation node that speaks the client intake:
    the flat server or a tree tier."""

    def __init__(self, kind: str):
        self.kind = kind

    def __call__(self, spec, anchor):
        from repro.agg.server import AggServer
        from repro.agg.tree import TIER_ID_BASE, TierAggregator
        if self.kind == "server":
            return AggServer(spec, anchor)
        return TierAggregator(spec, anchor, TIER_ID_BASE)

    @staticmethod
    def publish(node, anchor):
        """The mean of the node's accepted clients: the server's own, or a
        flat root's decode of the tier's forwarded combined payload."""
        from repro.agg.server import AggServer
        from repro.agg.transport import frame as wire
        if isinstance(node, AggServer):
            return node.finalize()[0]
        node.seal()
        root = AggServer(node.spec, anchor)
        for f in node.tick():
            if f[:4] == wire.MAGIC_PAYLOAD:
                root.receive(f)
        return root.finalize()[0]


@pytest.fixture(params=["server", "tier"])
def intake_node(request):
    """Runs a test once against each node kind that shares the intake."""
    return IntakeNode(request.param)
