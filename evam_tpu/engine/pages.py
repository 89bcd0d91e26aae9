"""Page accounting of the generate engine's page cache.

The cache itself is one device array ``[layers that attend, pages,
page_tokens, row width]`` that the step programs update in place
(engine/generate.py); its shape is the model family's (``state_shapes``:
576-value latent rows stored 640 wide in every layer of DeepSeek-V2 and
in Kimi-Linear's two latent-attention layers, a key and a value of 128 in
Jamba's two attention layers; a row is whole lane tiles of 128 in every
family, models/lm/common.py ``row_width``, so that the array lies
rows-minor on the chip). This is its host side, the same for
every family: which pages are free, which belong to a sequence, and which
are PINNED: the shared instruction prefix, mapped read-only into every
sequence's page table and never handed back. Page 0 is the null page:
rows of a step that carry no sequence read and write there. What a
family keeps per SLOT (recurrent state) is not paged and not counted
here. Only the engine's one thread calls in.
"""

from __future__ import annotations


class PagePool:
    def __init__(self, n_pages: int, page_tokens: int):
        if n_pages < 1:
            raise ValueError("a page pool holds at least the null page")
        self.n_pages = n_pages
        self.page_tokens = page_tokens
        self._free = list(range(n_pages - 1, 0, -1))
        self._pinned: frozenset[int] = frozenset()

    def pages_for(self, tokens: int) -> int:
        return -(-tokens // self.page_tokens)

    def alloc(self, n: int) -> list[int] | None:
        """``n`` pages, or None (and nothing taken) where fewer are
        free."""
        if n > len(self._free):
            return None
        taken = self._free[len(self._free) - n:]
        del self._free[len(self._free) - n:]
        return taken[::-1]

    def pin(self, n: int) -> list[int]:
        """``n`` pages that stay for the life of the pool."""
        pages = self.alloc(n)
        if pages is None:
            raise ValueError(f"the pool cannot pin {n} pages")
        self._pinned = self._pinned | frozenset(pages)
        return pages

    def free(self, pages: list[int]) -> None:
        for p in pages:
            if p in self._pinned or p == 0:
                raise ValueError(f"page {p} is shared and is never freed")
        self._free.extend(reversed(pages))

    @property
    def pinned(self) -> frozenset[int]:
        return self._pinned

    @property
    def in_use(self) -> int:
        """Pages held by a sequence or pinned (the null page is
        neither)."""
        return self.n_pages - 1 - len(self._free)

    @property
    def capacity(self) -> int:
        return self.n_pages - 1
