"""The plain statement of what ``engine/residency.py`` promises, a list and
a loop, importing nothing of the program (ISSUE 51).

``ByteLRU``: least recently used over ``(key, bytes)`` under a budget in
bytes, with pinned entries.  A put admits the newcomer, then the oldest
entry nobody holds leaves until the bytes fit or nothing evictable is left;
a release may be the room a store over its budget was waiting for.

``CountLRU``: the LRU by count that ``parallel.resident_datasets: <int>``
has always meant (``engine/residency.py::_LRU`` until PR 51): past the cap
the oldest leaves, held or not; a cap of 0 keeps nothing.
"""


class ByteLRU:
    def __init__(self, budget):
        self.budget = budget
        self.items = []           # [key, bytes, pins], oldest first
        self.evicted = []         # keys, in the order they left

    def get(self, key, pin=False):
        for i, item in enumerate(self.items):
            if item[0] == key:
                self.items.append(self.items.pop(i))
                item[2] += bool(pin)
                return True
        return False

    def put(self, key, nbytes, pin=False):
        for item in self.items:
            if item[0] == key:    # the first put won: no move, no resize
                item[2] += bool(pin)
                return
        self.items.append([key, nbytes, int(bool(pin))])
        self._shrink()

    def release(self, key):
        for item in self.items:
            if item[0] == key:
                item[2] -= 1
        self._shrink()

    def held(self):
        return sum(item[1] for item in self.items)

    def _shrink(self):
        while self.held() > self.budget:
            loose = [item for item in self.items if item[2] == 0]
            if not loose:
                return
            self.items.remove(loose[0])
            self.evicted.append(loose[0][0])


class CountLRU:
    def __init__(self, cap):
        self.cap, self.items = cap, []         # [key, value], oldest first
        self.hits = self.misses = 0
        self.evicted = []

    def get(self, key):
        for i, item in enumerate(self.items):
            if self.cap > 0 and item[0] == key:
                self.items.append(self.items.pop(i))
                self.hits += 1
                return item[1]
        self.misses += 1
        return None

    def put(self, key, value):
        if self.cap <= 0:
            return value
        for item in self.items:
            if item[0] == key:
                return item[1]
        self.items.append([key, value])
        while len(self.items) > self.cap:
            self.evicted.append(self.items.pop(0)[0])
        return value
