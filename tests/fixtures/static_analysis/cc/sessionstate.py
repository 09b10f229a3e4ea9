"""Seeded CC08 violations: session ring state written outside the
append seam (the compliant seam functions, `__init__` construction, and
ordinary attributes below must stay quiet)."""


class BadManager:
    def __init__(self, ring, cursor, length):
        # Construction is exempt: the state is being born, not mutated.
        self.session_ring = ring
        self.session_cursor = cursor
        self.session_length = length
        self._twin = {}
        self.lock = None

    def adopt(self, ring, cursor, length):  # analysis: session-append-seam
        """The legitimate seam: device state, host index and ledger hash
        move together under the lock."""
        self.session_ring = ring
        self.session_cursor = cursor
        self.session_length = length

    def prepare_chunk(self, groups, amounts,
                      now):  # analysis: session-append-seam
        """The tag may close a signature of several lines; the caller
        holds the lock, so the host index is this function's to write."""
        for a in groups:
            self._twin[a] = (amounts, now)

    def locked_reader(self, a):
        with self.lock:
            return self._twin.get(a)

    def unlocked_reader(self, a):
        return self._twin.get(a)  # expect: CC08

    def sneaky_rebind(self, ring):
        self.session_ring = ring  # expect: CC08
        self.session_cursor = None  # expect: CC08

    def sneaky_length_only(self, length):
        self.session_length = length  # expect: CC08


def bad_external_rebind(mgr, ring):
    mgr.session_ring = ring  # expect: CC08


def bad_tuple_rebind(mgr, a, b):
    mgr.session_ring, mgr.session_cursor = a, b  # expect: CC08


def bad_grouping_reads_the_index(mgr, ids):
    # what runs before the lock reads the chunk's ids alone
    return [mgr._twin.get(a) for a in ids]  # expect: CC08


def good_grouping(ids):
    index = {}
    return [index.setdefault(a, len(index)) for a in ids]


def good_other_attrs(mgr, ring):
    # Non-session attributes and reads are fine.
    mgr.pending_ring = ring
    mgr.ring = ring  # a hash ring, not session state
    return mgr.session_ring
