"""The benchmark's yardstick: everything a later PR may not change lives here."""
