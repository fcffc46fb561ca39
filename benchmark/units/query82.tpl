-- define [PRICE] = uniform_int(10, 60)
-- define [YEAR] = uniform_int(1998, 2002)
-- define [MONTH] = uniform_int(1, 7)
-- define [DAY] = uniform_int(10, 24)
-- note: TPC-DS draws INVDATE between January 1 and July 24 of [YEAR]; the
-- generator's grammar has no date domain, so the date is written from three
-- integer draws inside that range (as query20.tpl does).
-- note: TPC-DS draws four distinct MANUFACT ids from this list of twenty; the
-- generator's choice_n quotes what it draws, so each id is one draw from its own
-- quarter of the list (four distinct integers, as the specification's ulist gives).
-- define [M1] = choice(129, 270, 821, 423, 129)
-- define [M2] = choice(271, 917, 318, 561, 95)
-- define [M3] = choice(742, 134, 606, 882, 283)
-- define [M4] = choice(553, 651, 774, 818, 995)
SELECT i_item_id, i_item_desc, i_current_price
FROM item, inventory, date_dim, store_sales
WHERE i_current_price BETWEEN [PRICE] AND [PRICE] + 30
  AND inv_item_sk = i_item_sk
  AND d_date_sk = inv_date_sk
  AND d_date BETWEEN CAST('[YEAR]-0[MONTH]-[DAY]' AS DATE)
                 AND (CAST('[YEAR]-0[MONTH]-[DAY]' AS DATE) + INTERVAL 60 DAYS)
  AND i_manufact_id IN ([M1], [M2], [M3], [M4])
  AND inv_quantity_on_hand BETWEEN 100 AND 500
  AND ss_item_sk = i_item_sk
GROUP BY i_item_id, i_item_desc, i_current_price
ORDER BY i_item_id
LIMIT 100
