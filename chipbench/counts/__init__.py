"""Operations and bytes the algorithms need, computed from shapes."""
